"""Conv benchmark: materialized im2col+GEMM vs the fused implicit-im2col
kernels, per algo/dtype, on the AlexNet and ResNet-50 layer sets.

Writes ``benchmarks/BENCH_conv.json``. Both paths run the SAME Pallas GEMM
arithmetic with the SAME block shapes; the only difference is where the A
matrix lives:

  * materialized: Algorithm-1 gather into an HBM (B, M, K) array, then the
    GEMM kernel (``core.im2col.conv2d_via_gemm`` + ``kernels.ops.matmul``);
  * fused: the gather addresses are computed inside the kernel per (bm, bk)
    block — A exists only as VMEM tiles (``kernels.conv_gemm``).

CAVEAT printed with results: this container is CPU-only; interpret-mode
timings measure the emulation harness, not silicon. The load-bearing,
platform-independent number is ``im2col_bytes`` — the HBM traffic/footprint
the fused path deletes per image. Spatial dims are divided by ``--scale``
(default 4) to keep interpret-mode runtimes sane; the JSON records it.

    PYTHONPATH=src python benchmarks/conv_bench.py [--scale 4] [--limit 4]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import List

import jax.numpy as jnp

from repro.core import im2col, workloads
from repro.kernels import conv_gemm, ops as kops
from repro.tune import measure

OUT = pathlib.Path(__file__).resolve().parent / "BENCH_conv.json"

ALGOS = ("baseline", "fip", "ffip")
DTYPES = ("float32", "int8")


def _median_us(fn, *args, iters: int = 2) -> float:
    # repro.tune.measure owns the timing discipline (compile outside the
    # timed region, median-of-k) — one implementation for tuner and benches
    return measure.median_time_s(fn, *args, iters=iters) * 1e6


def _scaled_specs(name: str, scale: int, limit: int) -> List[workloads.ConvSpec]:
    """Distinct conv geometries of a model, spatial dims divided by
    ``scale`` (floor at the kernel size), deduped by everything that changes
    the kernels' work, largest-GEMM-first, capped at ``limit``."""
    seen = set()
    specs = []
    for s in workloads.CONV_SPECS[name]():
        h = max(s.kh, s.h // scale)
        w = max(s.kw, s.w // scale)
        scaled = workloads.ConvSpec(s.name, h, w, s.cin, s.cout, s.kh, s.kw,
                                    s.stride, s.pad, s.groups)
        key = (h, w, s.cin, s.cout, s.kh, s.kw, s.stride, s.pad, s.groups)
        if key not in seen:
            seen.add(key)
            specs.append(scaled)
    specs.sort(key=lambda s: -(s.oh * s.ow * s.k * s.cout))
    dropped = len(specs) - limit
    if limit and dropped > 0:
        print(f"[{name}] capping {len(specs)} distinct conv geometries to "
              f"{limit} (--limit); {dropped} smaller layers skipped")
        specs = specs[:limit]
    return specs


def _operands(spec: workloads.ConvSpec, batch: int, dtype: str):
    return measure._conv_operands(batch, spec.h, spec.w, spec.cin, spec.kh,
                                  spec.kw, spec.cout, spec.groups,
                                  jnp.dtype(dtype))


def bench_layer(spec: workloads.ConvSpec, *, batch: int, iters: int) -> dict:
    gemm_m = batch * spec.oh * spec.ow
    entry = {
        "name": spec.name,
        "h": spec.h, "w": spec.w, "cin": spec.cin, "cout": spec.cout,
        "kh": spec.kh, "kw": spec.kw, "stride": list(spec.stride),
        "pad": list(spec.pad), "groups": spec.groups,
        "gemm": {"m": gemm_m, "k": spec.k, "n": spec.cout // spec.groups,
                 "per_group": spec.groups},
        "im2col_bytes": {},          # per dtype: the HBM A-matrix footprint
        "results": {},
    }
    for dtype in DTYPES:
        x, kernel = _operands(spec, batch, dtype)
        itemsize = jnp.dtype(dtype).itemsize
        entry["im2col_bytes"][dtype] = (batch * spec.oh * spec.ow * spec.k
                                        * spec.groups * itemsize)
        for algo in ALGOS:
            bm, bn, bk = kops.choose_blocks(spec.oh * spec.ow,
                                            spec.cout // spec.groups,
                                            spec.k, algo)
            fused = lambda x_, k_: conv_gemm.conv_gemm_fused(
                x_, k_, stride=spec.stride, pad=spec.pad, groups=spec.groups,
                algo=algo, bm=bm, bn=bn, bk=bk)
            mat = lambda x_, k_: im2col.conv2d_via_gemm(
                x_, k_, stride=spec.stride, pad=spec.pad, groups=spec.groups,
                gemm_fn=lambda a, b: kops.matmul(a, b, algo=algo,
                                                 bm=bm, bn=bn, bk=bk))
            t_fused = _median_us(fused, x, kernel, iters=iters)
            t_mat = _median_us(mat, x, kernel, iters=iters)
            entry["results"][f"{algo}.{dtype}"] = {
                "blocks": {"bm": bm, "bn": bn, "bk": bk},
                "fused_us": round(t_fused, 1),
                "materialized_us": round(t_mat, 1),
                "fused_over_materialized": round(t_fused / max(t_mat, 1e-9), 3),
            }
    return entry


def write_bench(*, models=("alexnet", "resnet50"), scale: int = 4,
                limit: int = 4, batch: int = 1, iters: int = 2) -> dict:
    from repro.kernels.compat import device_kind
    prior = None
    if OUT.exists():
        try:
            prior = json.loads(OUT.read_text())
            prior.pop("baseline_prev", None)      # keep one generation
        except Exception:
            prior = None
    out = {
        "bench": "conv",
        "note": ("materialized = Algorithm-1 gather into HBM + Pallas GEMM; "
                 "fused = same GEMM arithmetic with the gather inside the "
                 "kernel (A only in VMEM tiles). Same blocks both sides. "
                 "CPU containers time interpret-mode emulation, not silicon; "
                 "im2col_bytes is the platform-independent HBM footprint the "
                 "fused path removes. Spatial dims divided by 'scale'."),
        "device_kind": device_kind(),
        "scale": scale,
        "batch": batch,
        "models": {},
    }
    for name in models:
        specs = _scaled_specs(name, scale, limit)
        layers = []
        for spec in specs:
            t0 = time.perf_counter()
            layers.append(bench_layer(spec, batch=batch, iters=iters))
            print(f"[{name}] {spec.name}: {spec.h}x{spec.w}x{spec.cin}"
                  f"->{spec.cout} k{spec.kh}x{spec.kw} g{spec.groups} "
                  f"({time.perf_counter() - t0:.1f}s)")
        out["models"][name] = {"layers": layers}
    if prior is not None:
        out["baseline_prev"] = prior
    OUT.write_text(json.dumps(out, indent=2) + "\n")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="alexnet,resnet50")
    ap.add_argument("--scale", type=int, default=4,
                    help="divide spatial dims (interpret-mode runtime knob)")
    ap.add_argument("--limit", type=int, default=4,
                    help="max distinct conv geometries per model (0 = all)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--iters", type=int, default=2)
    args = ap.parse_args()
    out = write_bench(models=tuple(m for m in args.models.split(",") if m),
                      scale=args.scale, limit=args.limit, batch=args.batch,
                      iters=args.iters)
    for name, m in out["models"].items():
        for layer in m["layers"]:
            for key, r in layer["results"].items():
                print(f"BENCH_conv.{name}.{layer['name']}.{key},"
                      f"fused={r['fused_us']}us,"
                      f"materialized={r['materialized_us']}us,"
                      f"ratio={r['fused_over_materialized']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
