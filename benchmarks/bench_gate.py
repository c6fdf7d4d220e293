"""CI gate over the committed serving benchmark: re-run a slice of
``serve_bench`` and hold it against ``benchmarks/BENCH_serve.json``.

    PYTHONPATH=src python benchmarks/bench_gate.py [--max-slowdown 5.0]

Two classes of check, per gated row (``mode`` x ``decode_chunk``):

* **Deterministic fields must match EXACTLY.** The workload is seeded and
  greedy, so ``completed``, ``tokens_out``, ``decode_steps``,
  ``decode_dispatches``, ``prefill_tokens``, ``decode_tokens`` and
  ``host_bytes_per_step`` are functions of the code, not the machine — any
  drift means the serving hot path changed behaviour without the committed
  bench being regenerated (run serve_bench.py and commit the new JSON).

* **Timing may only degrade within a generous bound.** CI machines are
  slower and noisier than the box that produced the committed numbers, so
  timings are gated one-sided: fresh ``decode_ms_per_step`` must stay under
  ``committed * --max-slowdown`` (default 5x). Speedups always pass. This
  catches order-of-magnitude regressions (a de-jitted hot path, a
  host-sync re-introduced per token) without flaking on CPU noise.

The re-run itself also re-executes every in-bench telemetry cross-check
(windowed TTFT/ITL percentiles vs raw request records, zero
``samples_dropped``, e2e reservoir non-overflow), so a metrics-layer
regression fails the gate even when the timings look fine.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

from serve_bench import bench  # noqa: E402  (same directory)

BENCH = pathlib.Path(__file__).resolve().parent / "BENCH_serve.json"

# (mode, decode_chunk) rows re-run by the gate: the float fast path at the
# chunking extremes plus the quantized fused path. Keep this slice small —
# the gate runs per-PR; the full sweep is serve_bench's job.
GATED_ROWS = (("float", 1), ("float", 4), ("int8-ffip", 4))

EXACT_FIELDS = ("completed", "tokens_out", "decode_steps",
                "decode_dispatches", "prefill_tokens", "decode_tokens",
                "host_bytes_per_step")


def gate(*, max_slowdown: float, rows=GATED_ROWS) -> list:
    committed = json.loads(BENCH.read_text())
    by_key = {(r["mode"], r["decode_chunk"]): r
              for r in committed.get("results", [])}
    problems = []
    for mode, chunk in rows:
        base = by_key.get((mode, chunk))
        if base is None:
            problems.append(f"{mode}/chunk{chunk}: no committed row in "
                            f"{BENCH.name} (regenerate with serve_bench.py)")
            continue
        fresh = bench("minicpm-2b", slots=base["slots"],
                      requests=base["requests"], max_new=4,
                      max_len=64, quantized=(mode != "float"),
                      decode_chunk=chunk)
        for f in EXACT_FIELDS:
            if fresh[f] != base[f]:
                problems.append(
                    f"{mode}/chunk{chunk}: {f} = {fresh[f]} != committed "
                    f"{base[f]} (behaviour changed; regenerate "
                    f"BENCH_serve.json if intentional)")
        limit = base["decode_ms_per_step"] * max_slowdown
        if fresh["decode_ms_per_step"] > limit:
            problems.append(
                f"{mode}/chunk{chunk}: decode_ms_per_step "
                f"{fresh['decode_ms_per_step']} > {limit:.2f} "
                f"(committed {base['decode_ms_per_step']} x "
                f"--max-slowdown {max_slowdown})")
        tag = f"{mode}/chunk{chunk}:"
        verdict = ("DRIFTED" if any(p.startswith(tag) for p in problems)
                   else "MATCH")
        print(f"bench-gate {mode}/chunk{chunk}: "
              f"decode {fresh['decode_ms_per_step']}ms/step "
              f"(committed {base['decode_ms_per_step']}, "
              f"limit {limit:.2f}), deterministic fields {verdict}")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-slowdown", type=float, default=5.0,
                    help="one-sided timing bound: fresh decode_ms_per_step "
                         "must stay under committed * this (default 5.0)")
    args = ap.parse_args(argv)
    problems = gate(max_slowdown=args.max_slowdown)
    if problems:
        print("bench-gate FAIL:\n  " + "\n  ".join(problems),
              file=sys.stderr)
        return 1
    print(f"bench-gate OK: {len(GATED_ROWS)} rows vs {BENCH.name}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
