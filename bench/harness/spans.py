"""The program's own spans, read beside the harness's steps and the device
trace.

The server records a span tree inside each ``BatchServer.step`` (``step``
over ``params``, ``admit`` with its ``prefill`` dispatches, ``decode`` and
``replay``; each dispatch split into ``.pack``, ``.dispatch`` and ``.sync``)
and an ``admit_wait`` span per request, from ``submit`` to the prefill that
admits it. It mirrors the tree on the profiler's clock as ``serve.<span>``
annotations on the ``/host:CPU`` plane. This module collects each step's
spans (:class:`SpanSession`), reduces them to the batcher's numbers
(:func:`queue_wait_p50_ms`, :func:`step_host_ms_p50`,
:func:`kv_live_share`), labels the device's idle gaps with the innermost
program span they fall in (:func:`program_label`,
:func:`idle_gaps_by_span`), and checks that the two clocks agree
(:func:`decode_inside_spans`, :func:`decode_shift_ms`,
:func:`idle_inside_spans`);
:func:`span_cost_us` times what one step's spans cost the host.

Like every reader, each reduction returns None, never 0, where it finds
nothing: a program without these spans gives None throughout.
"""
from __future__ import annotations

import bisect
import dataclasses
import time
from typing import Dict, Iterable, List, Optional, Tuple

from harness import serving
from harness import trace as tr_mod
from harness.readers import DECODE_PROGRAM, percentile, share

PREFIX = "serve."
SYNC = ("prefill.sync", "decode.sync")


@dataclasses.dataclass
class StepSpans:
    """The program's spans (``repro.obs.trace.Span``) that ended during one
    ``step`` call; ``lost`` when the ring dropped some of them before they
    were read."""
    spans: list
    lost: bool = False


def collect(tracer, t0: float, dropped0: int) -> StepSpans:
    """The spans of ``tracer`` that ended at or after ``t0``. The tracer
    keeps spans in the order they ended, so the walk stops at the first
    that ended before; if it reaches the ring's start after the ring
    dropped spans (``dropped0`` counts those dropped before), some were
    lost."""
    out = []
    reached = False
    for s in reversed(tracer.spans):
        if s.t1 < t0:
            reached = True
            break
        out.append(s)
    out.reverse()
    return StepSpans(out, lost=not reached and tracer.dropped > dropped0)


class SpanSession(serving.Session):
    """A :class:`harness.serving.Session` that also keeps, for each step of
    a run, the program's spans that ended in it (``step_spans``, by
    ``Step.index``)."""

    def run(self, seed: int, seconds: float, trace_dir=None) -> dict:
        self.step_spans: Dict[int, StepSpans] = {}
        self._dropped = self.server.tracer.dropped
        return super().run(seed, seconds, trace_dir)

    def _account(self, st, inflight) -> None:
        super()._account(st, inflight)
        tracer = self.server.tracer
        self.step_spans[st.index] = collect(tracer, st.t0, self._dropped)
        self._dropped = tracer.dropped


def _whole(steps: Iterable[StepSpans]):
    return (s for s in steps if not s.lost)


def queue_wait_p50_ms(steps: Iterable[StepSpans]) -> Optional[float]:
    """Median, over the ``admit_wait`` spans that ended in ``steps``, of
    the time from ``submit`` to the prefill dispatch that admitted the
    request: the batcher's own queue."""
    return percentile((1e3 * s.duration for st in _whole(steps)
                       for s in st.spans if s.name == "admit_wait"), 50)


def step_host_ms(st: StepSpans) -> Optional[float]:
    """The ``step`` span's duration less its ``.sync`` spans: host time in
    which the device gets no new work from the serving thread."""
    whole = [s.duration for s in st.spans if s.name == "step"]
    if len(whole) != 1:
        return None
    sync = sum(s.duration for s in st.spans if s.name in SYNC)
    return 1e3 * (whole[0] - sync)


def step_host_ms_p50(steps: Iterable[StepSpans]) -> Optional[float]:
    return percentile((v for v in map(step_host_ms, _whole(steps))
                       if v is not None), 50)


def kv_live_share(steps: Iterable[StepSpans]) -> Optional[float]:
    """K/V rows the decode dispatches attended over the rows their cache
    operand held, in percent, as the ``decode`` spans count them."""
    live = held = 0
    for st in _whole(steps):
        for s in st.spans:
            if s.name == "decode" and "live_rows" in s.attrs:
                live += s.attrs["live_rows"]
                held += s.attrs["cache_rows"]
    return share(live, held)


# -- the device trace ----------------------------------------------------------
def program_events(path) -> List[tr_mod.Interval]:
    """The ``serve.*`` annotations of a trace's ``/host:CPU`` plane, in
    time order."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(path))
    out = [tr_mod.Interval(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
           for plane in prof.planes if plane.name == "/host:CPU"
           for line in plane.lines for e in line.events
           if e.name.startswith(PREFIX)]
    out.sort(key=lambda e: e.t0)
    return out


def timeline(program: List[tr_mod.Interval]) -> List[tuple]:
    """Time-ordered disjoint (t0, t1, name) segments, each named by the
    innermost program span covering it. The program's spans nest (one
    thread opens them in ``with`` blocks), so the innermost is the latest
    to have started of those still open."""
    segs: List[tuple] = []
    stack: List[tr_mod.Interval] = []
    t = None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1].t1 <= limit:
            top = stack.pop()
            if top.t1 > t:
                segs.append((t, top.t1, top.name))
            t = max(t, top.t1)

    for e in sorted(program, key=lambda e: (e.t0, -e.t1)):
        if stack:
            close_until(e.t0)
        if stack and e.t0 > t:
            segs.append((t, e.t0, stack[-1].name))
        t = e.t0 if t is None else max(t, e.t0)
        stack.append(e)
    if stack:
        close_until(float("inf"))
    return segs


def seconds_by_span(segs: List[tuple], intervals: List[tuple]
                    ) -> Dict[str, float]:
    """Seconds of the time-ordered disjoint ``intervals`` by the innermost
    program span covering them (``none`` where no span does)."""
    out: Dict[str, float] = {}
    bounds = [(a, b) for a, b, _ in segs]
    for a, b in intervals:
        inside = 0.0
        k = bisect.bisect_right(bounds, (a, float("inf"))) - 1
        k = max(k, 0)
        while k < len(segs) and segs[k][0] < b:
            lo, hi = max(a, segs[k][0]), min(b, segs[k][1])
            if lo < hi:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + hi - lo
                inside += hi - lo
            k += 1
        if b - a > inside:
            out["none"] = out.get("none", 0.0) + (b - a - inside)
    return out


def program_label(segs: List[tuple], a: float, b: float) -> Optional[str]:
    """The innermost program span that covers most of [a, b], or None
    where no program span overlaps it."""
    by = seconds_by_span(segs, [(a, b)])
    by.pop("none", None)
    return max(by, key=by.get) if by else None


def gaps(dev: tr_mod.Device, lo: float, hi: float) -> List[Tuple[float, float]]:
    """The device's idle intervals within [lo, hi], in time order."""
    busy = tr_mod.union(dev.ops, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def intersect(xs: List[tuple], ys: List[tuple]) -> List[tuple]:
    """The intersection of two time-ordered lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_gaps_by_span(tr: tr_mod.Trace, segs: List[tuple],
                      dev: tr_mod.Device, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the traced window, each labelled
    ``<harness label>/<innermost program span>``, or with the harness
    label alone where no program span overlaps it."""
    longest = sorted(gaps(dev, *tr.window), key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in longest:
        inner = program_label(segs, a, b)
        lab = tr.label(a, b)
        out.append([lab if inner is None else f"{lab}/{inner}", b - a])
    return out


def idle_in_steps(tr: tr_mod.Trace, dev: tr_mod.Device) -> List[tuple]:
    """The device's idle intervals inside the harness's ``bench.step``
    annotations."""
    lo, hi = tr.window
    steps = [h for h in tr.host if h.name.startswith("bench.step")]
    return intersect(gaps(dev, lo, hi), tr_mod.union(steps, lo, hi))


def idle_inside_spans(tr: tr_mod.Trace, segs: List[tuple],
                      dev: tr_mod.Device) -> Optional[float]:
    """Share, in percent, of the device's idle seconds inside the harness's
    ``bench.step`` annotations that fall in a program span below
    ``serve.step``."""
    if not segs:
        return None
    by = seconds_by_span(segs, idle_in_steps(tr, dev))
    total = sum(by.values())
    inner = total - by.get("none", 0.0) - by.get(PREFIX + "step", 0.0)
    return 100.0 * inner / total if total > 0 else None


def decode_inside_spans(program: List[tr_mod.Interval],
                        dev: tr_mod.Device) -> Optional[float]:
    """Share, in percent, of the decode program's executions that start
    after a ``serve.decode.dispatch`` begins and end before the
    ``serve.decode.sync`` that follows it ends: how well the host's spans
    and the device's executions agree on one clock."""
    dispatch = [e for e in program if e.name == PREFIX + "decode.dispatch"]
    sync = [e for e in program if e.name == PREFIX + "decode.sync"]
    if not dispatch:
        return None
    ok = total = 0
    for m in dev.modules:
        if DECODE_PROGRAM not in m.name:
            continue
        total += 1
        d = [e for e in dispatch if e.t0 <= m.t0]
        if not d:
            continue
        after = [e for e in sync if e.t0 >= d[-1].t1]
        if after and m.t1 <= after[0].t1:
            ok += 1
    return 100.0 * ok / total if total else None


def decode_shift_ms(program: List[tr_mod.Interval], dev: tr_mod.Device
                    ) -> Optional[Tuple[float, float]]:
    """(lo, hi): the shifts of the device's timeline, in ms, that would put
    every decode execution inside its dispatch..sync spans, each paired with
    the ``serve.decode.dispatch`` span that starts nearest it. lo <= hi
    when one shift does: the spans and the device then agree up to an
    offset between the trace's host and device clocks, which
    :func:`decode_inside_spans` reads at zero."""
    dispatch = [e for e in program if e.name == PREFIX + "decode.dispatch"]
    sync = [e for e in program if e.name == PREFIX + "decode.sync"]
    lo, hi = float("-inf"), float("inf")
    for m in dev.modules:
        if DECODE_PROGRAM not in m.name or not dispatch:
            continue
        d = min(dispatch, key=lambda e: abs(e.t0 - m.t0))
        s = next((e for e in sync if e.t0 >= d.t1), None)
        if s is not None:
            lo, hi = max(lo, d.t0 - m.t0), min(hi, s.t1 - m.t1)
    return None if hi == float("inf") else (1e3 * lo, 1e3 * hi)


def span_cost_us(st: StepSpans, tracer, n: int = 2000) -> float:
    """Microseconds one step's spans cost the host: the tree under the
    ``step`` span of ``st`` opened and closed ``n`` times through
    ``tracer``, with the same names, nesting and attributes, and the step's
    other spans (``admit_wait``, ``request``) started and ended."""
    kids: Dict[Optional[int], list] = {}
    for s in st.spans:
        kids.setdefault(s.parent, []).append(s)
    tree = set()

    def walk(s):
        tree.add(s.sid)
        for c in kids.get(s.sid, ()):
            walk(c)

    roots = [s for s in st.spans if s.name == "step"]
    for r in roots:
        walk(r)
    flat = [s for s in st.spans if s.sid not in tree]

    def open_tree(s):
        with tracer.span(s.name, **s.attrs):
            for c in kids.get(s.sid, ()):
                open_tree(c)

    t = time.perf_counter()
    for _ in range(n):
        for r in roots:
            open_tree(r)
        for s in flat:
            tracer.end(tracer.start(s.name, **s.attrs))
    return 1e6 * (time.perf_counter() - t) / n
