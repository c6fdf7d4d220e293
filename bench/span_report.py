"""Report what the program's own spans show of one benchmark cell.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up and drives one window as ``bench/run.py`` does, with a
profiler trace of the window's last ``trace_seconds``, and keeps each step's
program spans (``harness/spans.py``). Prints one JSON line:

- over the window's steps, ``queue_wait_p50_ms`` (submit to the admitting
  prefill), ``step_host_ms_p50`` (a step's host time outside its ``.sync``
  spans) and ``kv_live_share`` (K/V rows decode attends over the rows its
  cache holds);
- from the trace, ``idle_gaps_by_span`` (the ten longest device idle gaps,
  labelled ``<harness label>/<innermost serve span>``), ``idle_by_span``
  (the device's idle seconds inside ``bench.step`` by innermost serve span),
  and checks that host spans and device share one clock:
  ``decode_inside_spans`` (decode executions inside their dispatch..sync
  spans, in percent), ``decode_shift_ms`` (the shifts of the device's
  timeline that would put them all inside) and ``idle_inside_spans``
  (idle seconds in a span below ``serve.step``, in percent);
- ``span_cost_us``: what one step's spans cost the host with no profiler
  session, replayed through a tracer like the server's, for a decode-only
  step and for a step that also admits; ``..._before`` replays only the
  spans the batcher opened before it had a span tree (``request``,
  ``prefill``, ``decode``, unmirrored), ``..._profiler_on`` the same
  spans while a profiler session records them.

A program without these spans reports null for each number. Runs on a
TPU only.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

from run import cache_everything, find_chips, note
from harness import spans, spec
from harness import trace as tr_mod

BEFORE = ("request", "prefill", "decode")


def cost(st, annotate):
    """Microseconds of ``st``'s spans: with this program's spans, with the
    spans it had before, and with this program's spans while a profiler
    session records them."""
    import jax
    from repro.obs import Tracer
    if st is None or annotate is None:
        return None, None, None
    old = spans.StepSpans([s for s in st.spans if s.name in BEFORE])
    now = spans.span_cost_us(st, Tracer(annotate=annotate))
    before = spans.span_cost_us(old, Tracer())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # as the benchmark traces
    with tempfile.TemporaryDirectory(prefix="span-cost-") as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            on = spans.span_cost_us(st, Tracer(annotate=annotate))
        finally:
            jax.profiler.stop_trace()
    return now, before, on


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    devs = find_chips(cell.chips)
    if devs is None:
        return 1
    cache_everything()
    sess = spans.SpanSession(cell, devs)
    sess.make_weights(args.seed)
    sess.build_server()
    sess.warm_up()
    trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="span-report-"))
    run = sess.run(args.seed, args.seconds, trace_dir)
    steps = [sess.step_spans[s.index] for s in run["steps"]
             if run["t0"] <= s.t0 and s.t1 <= run["t_stop"]]
    pb = next(trace_dir.rglob("*.xplane.pb"))
    tr = tr_mod.load(pb)
    program = spans.program_events(pb)
    shutil.rmtree(trace_dir, ignore_errors=True)
    dev = next(iter(tr.devices.values()))
    segs = spans.timeline(program)

    def names(st):
        return {s.name for s in st.spans}

    decode_only = next((st for st in steps if "decode" in names(st)
                        and not names(st) & {"prefill", "prefill_chunk"}),
                       None)
    admitting = next((st for st in steps if {"decode", "prefill"}
                      <= names(st)), None)
    annotate = getattr(sess.server.tracer, "annotate", None)
    c_dec, c_dec_before, c_dec_on = cost(decode_only, annotate)
    c_adm, c_adm_before, c_adm_on = cost(admitting, annotate)
    walls = sorted(1e3 * (s.t1 - s.t0) for s in run["steps"]
                   if run["t0"] <= s.t0 and s.t1 <= run["t_stop"])
    idle_by = spans.seconds_by_span(segs, spans.idle_in_steps(tr, dev))
    out = {
        "workload": cell.name, "seed": args.seed,
        "device": devs[0].device_kind,
        "steps": len(steps), "steps_lost": sum(st.lost for st in steps),
        "step_wall_ms_p50": walls[len(walls) // 2] if walls else None,
        "queue_wait_p50_ms": spans.queue_wait_p50_ms(steps),
        "step_host_ms_p50": spans.step_host_ms_p50(steps),
        "kv_live_share": spans.kv_live_share(steps),
        "trace_window_s": tr.window[1] - tr.window[0],
        "program_events": len(program),
        "idle_gaps_by_span": spans.idle_gaps_by_span(tr, segs, dev),
        "idle_by_span": dict(sorted(idle_by.items(), key=lambda kv: -kv[1])),
        "decode_inside_spans": spans.decode_inside_spans(program, dev),
        "decode_shift_ms": spans.decode_shift_ms(program, dev),
        "idle_inside_spans": spans.idle_inside_spans(tr, segs, dev),
        "span_cost_us": {"decode_step": c_dec,
                         "decode_step_before": c_dec_before,
                         "decode_step_profiler_on": c_dec_on,
                         "admit_step": c_adm,
                         "admit_step_before": c_adm_before,
                         "admit_step_profiler_on": c_adm_on},
    }
    note(f"{len(steps)} window steps, {len(program)} program events traced")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
