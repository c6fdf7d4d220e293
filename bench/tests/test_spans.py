"""The reductions of the program's own spans (``harness/spans.py``): the
batcher's numbers on a test-size run of a cell, and the labelling of idle
gaps on synthetic intervals and on the committed v5e trace, which predates
the program's span tree."""
from __future__ import annotations

import pathlib

import pytest

from harness import spans
from harness import trace as T

SEED = 2 ** 40 + 11
DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.mark.parametrize("config,mix", [
    ("minicpm-2b.float", "chat"), ("deepseek-coder-33b-l8.float",
                                   "code-backlog")])
def test_readers_on_a_test_size_run(cell_factory, config, mix):
    """Each request's queue wait lies within its time to first token, each
    step's host time within its wall time, and the live K/V share in
    (0, 100]."""
    sess = spans.SpanSession(cell_factory(config, mix))
    sess.make_weights(SEED)
    sess.build_server()
    sess.warm_up()
    run = sess.run(SEED, 1.5)
    window = [s for s in run["steps"]
              if run["t0"] <= s.t0 and s.t1 <= run["t_stop"]]
    steps = [sess.step_spans[s.index] for s in window]
    assert steps and not any(st.lost for st in steps)

    ttft = {str(r.rid): r.t_first - r.due for r in run["records"]
            if r.t_first}
    waits = [s for st in sess.step_spans.values() for s in st.spans
             if s.name == "admit_wait" and s.rid in ttft]
    assert waits
    for w in waits:
        assert 0 <= w.duration <= ttft[w.rid]
    assert spans.queue_wait_p50_ms(steps) >= 0
    for s, st in zip(window, steps):
        host = spans.step_host_ms(st)
        assert 0 <= host <= 1e3 * (s.t1 - s.t0)
    assert spans.step_host_ms_p50(steps) > 0
    assert 0 < spans.kv_live_share(steps) <= 100


def test_readers_find_nothing_without_the_spans():
    """Steps whose spans lack the tree read None, never 0, as does a step
    whose spans the ring dropped."""
    from repro.obs.trace import Span
    bare = spans.StepSpans([Span("decode", 1, t0=0.0, t1=1.0,
                                    attrs={"rids": [1], "chunk": 1}),
                               Span("prefill", 2, t0=1.0, t1=2.0,
                                    attrs={"bucket": 8, "rids": [1]})])
    lost = spans.StepSpans([Span("step", 3, t0=0.0, t1=1.0),
                               Span("admit_wait", 4, t0=0.0, t1=0.5)],
                           lost=True)
    for steps in ([bare], [lost], []):
        assert spans.queue_wait_p50_ms(steps) is None
        assert spans.step_host_ms_p50(steps) is None
        assert spans.kv_live_share(steps) is None


def _ev(name, a, b):
    return T.Interval(name, a, b)


# the device runs [0, 1], [3, 4], [6, 7.8], [8.2, 8.4] and [9, 10]; the
# host's step annotations cover [0, 8], a submit [8.5, 9]
DEVICE = T.Device(modules=[_ev("jit__decode_impl(1)", 3.0, 4.0),
                           _ev("jit__decode_impl(2)", 6.0, 7.0)],
                  ops=[_ev("%fusion.1 = f32[8] fusion(f32[8] %a)", a, b)
                       for a, b in ((0.0, 1.0), (3.0, 4.0), (6.0, 7.8),
                                    (8.2, 8.4), (9.0, 10.0))])
HOST = [_ev("bench.step:0", 0.0, 5.0), _ev("bench.step:1", 5.0, 8.0),
        _ev("bench.submit", 8.5, 9.0)]
PROGRAM = [_ev("serve.step", 0.2, 4.9),
           _ev("serve.decode", 1.1, 4.1),
           _ev("serve.decode.pack", 1.1, 2.9),
           _ev("serve.decode.dispatch", 2.9, 3.2),
           _ev("serve.decode.sync", 3.2, 4.1),
           _ev("serve.step", 5.1, 7.9),
           _ev("serve.decode", 5.6, 7.5),
           _ev("serve.decode.dispatch", 5.6, 6.2),
           _ev("serve.decode.sync", 6.2, 7.1)]


def test_timeline_names_the_innermost_span():
    segs = spans.timeline(PROGRAM)
    assert segs == [(0.2, 1.1, "serve.step"),
                    (1.1, 2.9, "serve.decode.pack"),
                    (2.9, 3.2, "serve.decode.dispatch"),
                    (3.2, 4.1, "serve.decode.sync"),
                    (4.1, 4.9, "serve.step"),
                    (5.1, 5.6, "serve.step"),
                    (5.6, 6.2, "serve.decode.dispatch"),
                    (6.2, 7.1, "serve.decode.sync"),
                    (7.1, 7.5, "serve.decode"),
                    (7.5, 7.9, "serve.step")]


def test_program_label_and_idle_gaps_by_span_on_synthetic_events():
    """The innermost program span wins; a gap outside every program span
    keeps the harness's label alone."""
    tr = T.Trace(devices={"/device:TPU:0": DEVICE}, host=HOST)
    segs = spans.timeline(PROGRAM)
    assert spans.program_label(segs, 1.0, 3.0) == "serve.decode.pack"
    assert spans.program_label(segs, 4.0, 6.0) == "serve.step"
    assert spans.program_label(segs, 8.0, 9.0) is None
    got = spans.idle_gaps_by_span(tr, segs, DEVICE)
    assert got == [["bench.step/serve.decode.pack", pytest.approx(2.0)],
                   ["bench.step/serve.step", pytest.approx(2.0)],
                   ["bench.submit", pytest.approx(0.6)],
                   ["bench.step/serve.step", pytest.approx(0.4)]]
    # every gap keeps its harness label in front, in the same order
    plain = sorted(T.idle_gaps(tr, DEVICE, *tr.window), key=lambda g: -g[1])
    assert [g[0].split("/")[0] for g in got] == [lab for lab, _ in plain]


def test_clock_checks_on_synthetic_events():
    """Both decode executions lie within their dispatch..sync spans, and
    one that outlasts its sync span does not; executions that all start
    early by one offset fit after one shift of the device's timeline; of the 4.2 idle seconds inside
    step annotations, [4.9, 5.1] and [7.9, 8] fall outside every program
    span and 1.5 s in ``serve.step`` alone."""
    tr = T.Trace(devices={"/device:TPU:0": DEVICE}, host=HOST)
    segs = spans.timeline(PROGRAM)
    assert spans.decode_inside_spans(PROGRAM, DEVICE) == pytest.approx(100.0)
    lo, hi = spans.decode_shift_ms(PROGRAM, DEVICE)
    assert (lo, hi) == (pytest.approx(-100.0), pytest.approx(100.0))
    late = T.Device(modules=[_ev("jit__decode_impl(3)", 3.0, 4.6)], ops=[])
    assert spans.decode_inside_spans(PROGRAM, late) == pytest.approx(0.0)
    lo, hi = spans.decode_shift_ms(PROGRAM, late)     # too long to fit
    assert lo > hi
    early = T.Device(modules=[_ev("jit__decode_impl(4)", 2.5, 3.5),
                              _ev("jit__decode_impl(5)", 5.3, 6.3)], ops=[])
    assert spans.decode_inside_spans(PROGRAM, early) == pytest.approx(0.0)
    lo, hi = spans.decode_shift_ms(PROGRAM, early)    # one shift fits both
    assert (lo, hi) == (pytest.approx(400.0), pytest.approx(600.0))
    by = spans.seconds_by_span(segs, spans.idle_in_steps(tr, DEVICE))
    assert sum(by.values()) == pytest.approx(4.2)
    assert by["none"] == pytest.approx(0.3)
    assert by["serve.step"] == pytest.approx(1.5)
    assert spans.idle_inside_spans(tr, segs, DEVICE) == pytest.approx(
        100 * 2.4 / 4.2)


def test_committed_trace_falls_back_to_harness_labels():
    """The committed trace holds no program spans: the gaps by span are
    the ten longest idle gaps under the harness's labels, and the clock
    checks find nothing to read."""
    tr = T.load(DATA)
    program = spans.program_events(DATA)
    assert program == []
    dev = tr.devices["/device:TPU:0"]
    plain = sorted(T.idle_gaps(tr, dev, *tr.window), key=lambda g: -g[1])
    got = spans.idle_gaps_by_span(tr, spans.timeline(program), dev)
    assert got == [[lab, s] for lab, s in plain[:10]]
    assert spans.decode_inside_spans(program, dev) is None
    assert spans.decode_shift_ms(program, dev) is None
    assert spans.idle_inside_spans(tr, [], dev) is None


def test_span_cost_replays_the_step_tree():
    """The cost replay opens the step's tree with its names and nesting
    through the given tracer, and starts and ends its other spans."""
    from repro.obs import Tracer
    from repro.obs.trace import Span
    tree = [Span("params", 2, parent=1), Span("decode.sync", 4, parent=3),
            Span("decode", 3, parent=1, attrs={"live_rows": 5}),
            Span("admit_wait", 5, parent=9), Span("step", 1)]
    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    tracer = Tracer(annotate=Recorder, capacity=64)
    us = spans.span_cost_us(spans.StepSpans(tree), tracer, n=3)
    assert us > 0
    assert opened == ["step", "params", "decode", "decode.sync"] * 3
    ring = list(tracer.spans)[:5]
    assert [s.name for s in ring] == ["params", "decode.sync", "decode",
                                      "step", "admit_wait"]
    by = {s.name: s for s in ring}
    assert by["decode"].parent == by["step"].sid
    assert by["decode.sync"].parent == by["decode"].sid
    assert by["decode"].attrs == {"live_rows": 5}
