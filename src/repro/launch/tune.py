"""Offline kernel autotuner CLI — pre-populates the repro.tune schedule cache.

    # tune the GEMM shape set of a model config (dense/MoE/attention
    # projections + the tied unembed), plus its flash-attention buckets:
    PYTHONPATH=src python -m repro.launch.tune --arch minicpm-2b --smoke \
        --m 4,64 --budget 4

    # tune a CNN workload's conv-as-GEMM shape table (core.workloads):
    PYTHONPATH=src python -m repro.launch.tune --workload alexnet \
        --dtypes int8 --budget 6

Shapes are bucketed (pow2 per dim) and deduped before measuring, so the cost
is one tuning run per distinct bucket, not per layer. A warm cache is a
no-op: already-tuned buckets are reported as ``cached`` with ZERO
re-measurement — ``--expect-cached`` turns that into a hard assertion (the CI
tune-smoke job runs the tuner twice and requires the second run to measure
nothing). Serving picks the schedules up via ``--gemm-block auto``
(launch.serve / BatchServer) and ``GemmConfig(block="auto")``.

The ``--workload`` path tunes the conv-as-GEMM shape tables (the
materializing path); FUSED implicit-im2col conv schedules are tuned at real
conv geometry by ``python -m repro.launch.vision --model X --tune`` instead
(conv-specific candidate space: bk aligned to Cin_g*KW multiples).
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro import configs, tune
from repro.core import workloads
from repro.models.model import build_model
from repro.tune import measure


def _arch_gemm_shapes(cfg, m_values: List[int]) -> List[Tuple[int, int, int]]:
    """(m, k, n) set for a model config: every dense ``w`` leaf (attention /
    MLP / MoE projections — leading stacked-layer dims stripped) plus the
    tied-embedding unembed, crossed with the caller's M values (tokens per
    dispatch: decode = slots, prefill = slots x bucket)."""
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    kn: set = set()

    def walk(node):
        if isinstance(node, dict):
            w = node.get("w")
            if w is not None and not isinstance(w, dict) and w.ndim >= 2:
                kn.add((int(w.shape[-2]), int(w.shape[-1])))
            tbl = node.get("table")
            if tbl is not None and not isinstance(tbl, dict) and tbl.ndim == 2:
                kn.add((int(tbl.shape[1]), int(tbl.shape[0])))  # unembed d->V
            for v in node.values():
                walk(v)

    walk(params)
    return [(m, k, n) for m in m_values for (k, n) in sorted(kn)]


def _workload_gemm_shapes(name: str, batch: int) -> List[Tuple[int, int, int]]:
    return [(g.m, g.k, g.n) for g in workloads.MODELS[name](batch)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="pre-populate the repro.tune kernel schedule cache")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--arch", choices=sorted(configs.ARCHS))
    src.add_argument("--workload", choices=sorted(workloads.MODELS))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config for --arch")
    ap.add_argument("--batch", type=int, default=1, help="--workload batch")
    ap.add_argument("--m", default="4,64,256",
                    help="comma-separated M values (tokens per dispatch) "
                         "crossed with the --arch (K, N) set")
    ap.add_argument("--slots", default="2,4",
                    help="comma-separated serving batch sizes for the --arch "
                         "flash buckets (prefill runs BH = slots x heads)")
    ap.add_argument("--seq", default="16,64",
                    help="comma-separated sequence lengths (prompt buckets) "
                         "for the --arch flash-attention jobs")
    ap.add_argument("--algos", default="baseline,fip,ffip")
    ap.add_argument("--dtypes", default="float32,int8")
    ap.add_argument("--budget", type=int, default=0,
                    help="max candidates per bucket (0 = full space)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timing repetitions per candidate (median wins)")
    ap.add_argument("--limit", type=int, default=0,
                    help="cap the number of distinct buckets tuned (0 = all)")
    ap.add_argument("--no-flash", action="store_true",
                    help="skip flash-attention tuning for --arch")
    ap.add_argument("--expect-cached", action="store_true",
                    help="fail if anything had to be measured (warm-cache "
                         "assertion for CI)")
    ap.add_argument("--refresh-artifact", default=None, metavar="DIR",
                    help="after tuning, re-slice this repro.prepare "
                         "artifact's schedule from the cache and re-save it "
                         "(ships fresh schedules with the prepared weights)")
    args = ap.parse_args(argv)

    m_values = [int(x) for x in args.m.split(",") if x]
    algos = [a for a in args.algos.split(",") if a]
    dtypes = [jnp.dtype(d) for d in args.dtypes.split(",") if d]

    flash_jobs: List[Tuple[int, int, int, int]] = []
    if args.arch:
        cfg = configs.get_config(args.arch)
        if args.smoke:
            cfg = configs.smoke_config(cfg)
        shapes = _arch_gemm_shapes(cfg, m_values)
        if not args.no_flash:
            # q/k head dim as _flash_sdpa sees it: MLA prefill runs flash on
            # the decompressed nope+rope heads, everything else on cfg.hd.
            hd = (cfg.mla.nope_head_dim + cfg.mla.rope_head_dim
                  if cfg.mla is not None else cfg.hd)
            # key on the SERVING geometry: bucketed prefill dispatches the
            # forward over all batch_slots rows at the prompt-bucket width,
            # so flash sees BH = slots x heads and sq = sk = bucket. (The
            # --m values are tokens-per-dispatch for GEMMs, not batches.)
            flash_jobs = [(cfg.n_heads * b, s, s, hd)
                          for b in (int(x) for x in args.slots.split(",") if x)
                          for s in (int(x) for x in args.seq.split(",") if x)]
        label = cfg.name
    else:
        shapes = _workload_gemm_shapes(args.workload, args.batch)
        label = args.workload

    cache = tune.get_cache()
    timed0 = measure.counters["timed_candidates"]
    seen, jobs = set(), []
    for (m, k, n) in shapes:
        for algo in algos:
            for dt in dtypes:
                key = tune.gemm_key(algo, dt, m, n, k)
                if key not in seen:
                    seen.add(key)
                    jobs.append((key, m, k, n, algo, dt))
    if args.limit:
        # one cap over GEMM + flash buckets combined (GEMM jobs first)
        jobs = jobs[:args.limit]
        flash_jobs = flash_jobs[:max(0, args.limit - len(jobs))]

    t0 = time.perf_counter()
    measured = cached = 0
    for key, m, k, n, algo, dt in jobs:
        pre = measure.counters["timed_candidates"]
        entry = tune.tune_gemm(m, n, k, dt, algo=algo, budget=args.budget,
                               iters=args.iters, cache=cache, persist=False)
        fresh = measure.counters["timed_candidates"] > pre
        measured += fresh
        cached += not fresh
        b = entry["blocks"]
        status = "tuned " if fresh else "cached"
        print(f"[{status}] gemm {algo:8s} {jnp.dtype(dt).name:7s} "
              f"m{m} k{k} n{n} -> bm={b['bm']} bn={b['bn']} bk={b['bk']} "
              f"({entry['us']}us, {entry['candidates']} candidates)")

    flash_seen: set = set()
    for bh, sq, sk, d in flash_jobs:
        fkey = tune.flash_key(jnp.float32, bh, sq, sk, d)
        if fkey in flash_seen:       # slot counts sharing a pow2 BH bucket
            continue
        flash_seen.add(fkey)
        pre = measure.counters["timed_candidates"]
        entry = tune.tune_flash(bh, sq, sk, d, budget=args.budget,
                                iters=args.iters, cache=cache, persist=False)
        fresh = measure.counters["timed_candidates"] > pre
        measured += fresh
        cached += not fresh
        b = entry["blocks"]
        status = "tuned " if fresh else "cached"
        print(f"[{status}] flash fwd float32 bh{bh} sq{sq} sk{sk} d{d} "
              f"-> bq={b['bq']} bk={b['bk']} ({entry['us']}us)")

    if measured:
        cache.save()   # one write for the whole sweep, not one per bucket
    dt_s = time.perf_counter() - t0
    timed = measure.counters["timed_candidates"] - timed0
    print(f"{label}: {measured} buckets tuned / {cached} reused from cache "
          f"({timed} candidates timed, {dt_s:.1f}s) -> {cache.path}")
    if args.expect_cached and measured:
        print("--expect-cached: FAIL — warm cache still measured",
              file=sys.stderr)
        return 1
    if args.refresh_artifact:
        from repro import prepare
        from repro.kernels import compat
        pm = prepare.load(args.refresh_artifact)
        # re-slice for THIS device (the one we just tuned on) and re-stamp —
        # this is also the sanctioned way to re-home an artifact whose
        # schedule slice was dropped on a foreign device_kind.
        pm.device = compat.device_kind()
        pm.schedule = cache.entries_for_device(pm.device)
        pm.save(args.refresh_artifact)
        print(f"refreshed {args.refresh_artifact}: "
              f"{len(pm.schedule)} schedule entries for {pm.device}")
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
