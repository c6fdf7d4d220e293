"""GEMM micro-benchmarks: the three arithmetic paths, timed on this host.

CAVEAT printed with results: this container is CPU-only; interpret-mode Pallas
timings measure the emulation harness, not TPU silicon. The load-bearing
numbers are the arithmetic-complexity counters (measured multiplies via jaxpr
instrumentation), which are platform-independent — those are the paper's Eq.5/6.

``python benchmarks/gemm_micro.py`` additionally runs the repro.tune
autotuner over each pallas kernel/dtype and writes
``benchmarks/BENCH_gemm.json`` (the BENCH_serve.json convention):
default-block vs tuned-block timings per kernel/dtype with the static
defaults preserved under a ``baseline_default`` key (and any previous file's
results under ``baseline_prev``), so the tuning win — and the machine it was
measured on — stays visible in one artifact.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import List

import jax
import jax.numpy as jnp

from repro.core import analytical as an
from repro.core import fip
from repro.kernels import ops

OUT = pathlib.Path(__file__).resolve().parent / "BENCH_gemm.json"


def _time(fn, *args, iters: int = 3) -> float:
    # warmup: ONE call (jax.block_until_ready handles tuples/pytrees). The
    # old isinstance-probe evaluated fn(*args) twice, doubling compile+run
    # warmup cost for every timed entry.
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> List[str]:
    rows = ["gemm_micro.name,us_per_call,derived"]
    key = jax.random.PRNGKey(0)
    for m, k, n in [(256, 256, 256), (512, 1024, 512)]:
        ka, kb = jax.random.split(key)
        a = jax.random.normal(ka, (m, k), jnp.float32)
        b = jax.random.normal(kb, (k, n), jnp.float32)
        t_xla = _time(jax.jit(lambda a, b: a @ b), a, b)
        t_ref_fip = _time(jax.jit(lambda a, b: fip.fip_matmul(a, b, k_chunk=32)), a, b)
        rows.append(f"gemm_micro.xla_base_{m}x{k}x{n},{t_xla:.0f},")
        rows.append(f"gemm_micro.fip_ref_{m}x{k}x{n},{t_ref_fip:.0f},cpu-emulation-only")
        # measured multiply counts (the real claim):
        mb = fip.count_multiplies_in_jaxpr(lambda a, b: a @ b, a, b)
        mf = fip.count_multiplies_in_jaxpr(lambda a, b: fip.fip_matmul(a, b), a, b)
        rows.append(f"gemm_micro.mults_{m}x{k}x{n},{mf},"
                    f"ratio_vs_baseline={mf / mb:.4f} (Eq.5: "
                    f"{an.fip_mults(m, k, n) / an.baseline_mults(m, k, n):.4f})")
    # pallas kernels (interpret) on a small tile — correctness-mode timing
    a = jax.random.normal(key, (128, 128), jnp.float32)
    b = jax.random.normal(key, (128, 128), jnp.float32)
    for algo in ("baseline", "fip", "ffip"):
        t = _time(lambda a, b, al=algo: ops.matmul(a, b, algo=al, interpret=True),
                  a, b, iters=2)
        rows.append(f"gemm_micro.pallas_{algo}_128_interpret,{t:.0f},interpret-mode")
    return rows


def tuned_vs_default(*, shapes=((256, 256, 256),),
                     algos=("baseline", "fip", "ffip"),
                     dtypes=("float32", "int8"),
                     budget: int = 6, iters: int = 3, cache=None) -> dict:
    """Autotune each pallas kernel/dtype over ``shapes`` and report default
    vs tuned blocks + timings. Both numbers come from the SAME search sweep
    (the default is always candidate 0), so ``tuned_us <= default_us`` by
    construction and a warm cache re-measures NOTHING; only a cache entry
    tuned by an older build that lacks its default timing triggers a local
    re-measure of the two configurations."""
    from repro import tune
    from repro.tune import measure as tmeasure

    results = {}
    for (m, k, n) in shapes:
        for algo in algos:
            for dtype in dtypes:
                entry = tune.tune_gemm(m, n, k, jnp.dtype(dtype), algo=algo,
                                       budget=budget, iters=iters, cache=cache)
                tuned = entry["blocks"]
                default = entry["default_blocks"]
                t_tun, t_def = entry["us"], entry.get("default_us")
                if t_def is None:
                    a, b = tmeasure._gemm_operands(m, k, n, jnp.dtype(dtype))
                    t_def = round(tmeasure.time_gemm_blocks(
                        algo, a, b,
                        (default["bm"], default["bn"], default["bk"]),
                        iters=iters) * 1e6, 1)
                    t_tun = round(tmeasure.time_gemm_blocks(
                        algo, a, b, (tuned["bm"], tuned["bn"], tuned["bk"]),
                        iters=iters) * 1e6, 1)
                results[f"{algo}.{dtype}.{m}x{k}x{n}"] = {
                    "default_blocks": default,
                    "default_us": t_def,
                    "tuned_blocks": tuned,
                    "tuned_us": t_tun,
                    "speedup": round(t_def / max(t_tun, 1e-12), 3),
                    "search_candidates": entry["candidates"],
                }
    return results


def write_bench(*, budget: int = 6, iters: int = 3, shapes=None) -> dict:
    """Write benchmarks/BENCH_gemm.json (default-vs-tuned per kernel/dtype)."""
    from repro import tune

    shapes = shapes or ((256, 256, 256),)
    prior = None
    if OUT.exists():
        try:
            prior = json.loads(OUT.read_text())
            prior.pop("baseline_prev", None)   # keep one generation, not all
        except Exception:
            prior = None
    results = tuned_vs_default(shapes=shapes, budget=budget, iters=iters)
    out = {
        "bench": "gemm",
        "note": ("CPU containers time the interpret-mode harness, not "
                 "silicon; the tuned-vs-default ratio on THIS device_kind is "
                 "the load-bearing number. baseline_default = the static "
                 "blocks the kernels ship with (always search candidate 0); "
                 "default_us/tuned_us come from the same median-of-k search "
                 "sweep, so tuned <= default by construction and a warm "
                 "cache run re-measures nothing."),
        "device_kind": tune.device_kind(),
        "cache": str(tune.get_cache().path),
        "baseline_default": {k: {"blocks": v["default_blocks"],
                                 "us": v["default_us"]}
                             for k, v in results.items()},
        "results": results,
    }
    if prior is not None:
        out["baseline_prev"] = prior
    OUT.write_text(json.dumps(out, indent=2) + "\n")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=6,
                    help="max tuning candidates per kernel/dtype")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--shape", default="256,256,256",
                    help="m,k,n for the tuned-vs-default comparison")
    args = ap.parse_args()
    for r in run():
        print(r)
    m, k, n = (int(x) for x in args.shape.split(","))
    out = write_bench(budget=args.budget, iters=args.iters,
                      shapes=((m, k, n),))
    for name, r in out["results"].items():
        print(f"BENCH_gemm.{name},default={r['default_us']}us"
              f"({r['default_blocks']}),tuned={r['tuned_us']}us"
              f"({r['tuned_blocks']}),speedup={r['speedup']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
