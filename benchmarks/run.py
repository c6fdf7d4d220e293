"""Benchmark orchestrator — one section per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run
Prints ``name,value,...`` CSV blocks; each maps to a paper artifact:
  fig2.*    PE register model (Eqs. 17-19)
  fig9.*    MXU sweep: resources/frequency/throughput + fit limits
  table1.*  8-bit FFIP vs paper Table 1 (GOPS et al.)
  table2.*  16-bit FFIP vs paper Table 2
  table3.*  ops/multiplier/cycle vs best prior works (Table 3)
  sec6p1.*  baseline vs FIP vs FFIP core claims
  fig9x.*   modeled vs measured cross-check (reads benchmarks/BENCH_conv.json)
  gemm_micro.*  arithmetic-complexity measurements + host timings
  roofline.*    TPU dry-run roofline summary (reads benchmarks/results/dryrun)
"""
from __future__ import annotations

import json
import pathlib


def roofline_summary():
    rows = ["roofline.cell,bottleneck,compute_s,memory_s,collective_s,roofline_frac,status"]
    d = pathlib.Path(__file__).parent / "results" / "dryrun"
    if not d.exists():
        rows.append("roofline.none,-,-,-,-,-,run launch.dryrun first")
        return rows
    for f in sorted(d.glob("*__16x16.json")):
        r = json.loads(f.read_text())
        if r.get("status") == "ok":
            rows.append(
                f"roofline.{r['arch']}__{r['shape']},{r['bottleneck']},"
                f"{r['compute_s']:.4f},{r['memory_s']:.4f},"
                f"{r['collective_s']:.4f},{r['roofline_fraction']:.3f},ok")
        else:
            rows.append(f"roofline.{r['arch']}__{r['shape']},-,-,-,-,-,{r['status']}")
    return rows


def main() -> None:
    from benchmarks import accel_tables, gemm_micro
    sections = [
        accel_tables.fig2_registers(),
        accel_tables.fig9_sweep(),
        accel_tables.table1(),
        accel_tables.table2(),
        accel_tables.table3(),
        accel_tables.fip_vs_ffip_vs_baseline(),
        accel_tables.fig9_measured_crosscheck(),
        gemm_micro.run(),
        roofline_summary(),
    ]
    for rows in sections:
        for r in rows:
            print(r)
        print()


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
