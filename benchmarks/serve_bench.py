"""Serving benchmark: continuous-batching throughput + per-phase timings.

    PYTHONPATH=src python benchmarks/serve_bench.py [--arch minicpm-2b]

Runs the continuous batcher (float and int8-FFIP quantized modes) over a
stream of mixed-length requests, sweeping the fused-decode ``decode_chunk``
knob, and writes ``benchmarks/BENCH_serve.json``: tok/s, steps/s, the
prefill / decode / host-overhead split from BatchServer.stats, per-step host
transfer, TTFT, e2e p50/p99 request latency, and compile counts.

Schema note (since the repro.obs subsystem landed): the latency percentiles
in ``results*`` — ``e2e_ms`` per contiguous/paged row and ``e2e_fake_s`` in
``results_faults`` — are computed from the obs latency histograms
(``serve_request_e2e_seconds`` / ``router_request_e2e_seconds``, each run on
its own fresh ``obs.Registry``), cross-checked in-process against the raw
per-request records (exact-reservoir quantiles, so the numbers are
bit-comparable with the pre-obs percentile math). ``ttft_ms`` p50/p99 and
``itl_ms`` come from the WINDOWED histograms
(``serve_ttft_window_seconds`` / ``serve_itl_window_seconds``, window pinned
to 3600 s so the whole timed run stays live), likewise cross-checked against
the raw per-request lists with a zero-``samples_dropped`` assertion — the
bench is the proof the SLO-facing windowed percentiles are exact. Numbers
measured before earlier refactors stay verbatim under ``baseline_pr2`` /
``baseline_prev``.

``results_faults`` drives the multi-replica router with 1-of-3 replicas
flapping on a seeded FaultPlan (raise/hang, fake clock) and records outcome
counts, retries/failovers/quarantines, and the e2e latency tax of failover
vs the identical fleet with no faults — asserting every completion stays
token-identical to the no-fault run (``--skip-faults`` skips it).

Jit warmup runs OUTSIDE the timed region (a covering workload — every prompt
bucket plus a decode dispatch — compiles first; its wall time is reported
separately as ``compile_s``), so the timed numbers are steady-state serving.
The PR 2 hot path (host-side argmax over (B, V) logits, one dispatch per
token, one prefill compile per prompt length, warmup inside the timed
region) is kept in the file verbatim under ``baseline_pr2``, and the
contiguous-cache numbers measured immediately before the block-paged KV
change live under ``baseline_prev`` — so the trajectory stays visible in one
file; ``comparison`` reports the decode speedup and the host-transfer
reduction against PR 2.

The paged section (``results_paged``) runs the block-paged KV cache on a
shared-prefix workload plus one long prompt, with chunked prefill, over a
WARM prefix cache (the untimed warmup run registers the prefix pages), and
records the paged-only metrics: pages_peak vs the contiguous-equivalent page
count, resident prefix-cache pages after drain, prefix_hit_tokens,
cow_copies, prefill_chunks, page-table upload bytes, and TTFT under the
long-prefill + decode mix. ``comparison_paged`` re-runs the identical mix on
the contiguous cache and reports the TTFT and footprint side by side (and
asserts the paged gather outputs are byte-identical to contiguous).

``results_prepared`` times the repro.prepare warm-start contract: cold
in-process offline prep (int8 quantization + Eq. 9 y-deltas) vs saving and
loading the serialized artifact, then serves from the loaded artifact and
asserts ``recomputed == 0``. ``results_tp`` sweeps tensor-parallel decode
(BatchServer ``mesh=``, model axis 1/2/4 over the visible devices — force
host devices with XLA_FLAGS to sweep past 1) and asserts output tokens stay
identical across TP widths.

CAVEAT (same as gemm_micro): this container is CPU-only, so absolute timings
measure the XLA-CPU + interpret-mode harness, not accelerator silicon — the
load-bearing outputs are the phase RATIOS, the chunk-sweep trend, the
host-transfer reduction, and the paged footprint/prefix-hit counters, which
show what the fused hot path and the paged cache amortize.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import numpy as np

import repro.obs as obs
from repro import configs
from repro.models.model import build_model
from repro.serve.batcher import BatchServer, Request

OUT = pathlib.Path(__file__).resolve().parent / "BENCH_serve.json"

# PR 2 numbers measured in this container on the identical workload
# (minicpm-2b-smoke, 4 slots, 6 requests, max_new=4, seed 0) with the PR 2
# hot path. Kept verbatim so the trajectory stays visible in one file.
BASELINE_PR2 = [
    {"arch": "minicpm-2b-smoke", "mode": "float", "slots": 4, "requests": 6,
     "tokens_out": 24, "decode_steps": 6, "wall_s": 4.921, "tok_per_s": 4.88,
     "phase_s": {"prefill": 4.121, "decode": 0.615, "host_other": 0.186},
     "decode_ms_per_step": 102.42},
    {"arch": "minicpm-2b-smoke", "mode": "int8-ffip", "slots": 4,
     "requests": 6, "tokens_out": 24, "decode_steps": 6, "wall_s": 14.343,
     "tok_per_s": 1.67,
     "phase_s": {"prefill": 10.156, "decode": 1.882, "host_other": 2.306},
     "decode_ms_per_step": 313.59},
]

# Contiguous-cache numbers measured in this container immediately before the
# multi-replica router landed (same sweep, same workload/seed as below), so
# the router refactor's effect on the untouched single-server hot path stays
# auditable: the contiguous sweep in ``results`` should match these within
# CPU noise.
BASELINE_PREV = [
    {"mode": "float", "decode_chunk": 1, "tok_per_s": 2282.0,
     "steps_per_s": 1045.13, "decode_ms_per_step": 0.96,
     "host_bytes_per_step": 16.0},
    {"mode": "float", "decode_chunk": 2, "tok_per_s": 2682.74,
     "steps_per_s": 1372.14, "decode_ms_per_step": 0.73,
     "host_bytes_per_step": 21.3},
    {"mode": "float", "decode_chunk": 4, "tok_per_s": 2423.44,
     "steps_per_s": 1138.85, "decode_ms_per_step": 0.88,
     "host_bytes_per_step": 21.3},
    {"mode": "float", "decode_chunk": 8, "tok_per_s": 2772.5,
     "steps_per_s": 1460.74, "decode_ms_per_step": 0.68,
     "host_bytes_per_step": 42.7},
    {"mode": "int8-ffip", "decode_chunk": 1, "tok_per_s": 1096.68,
     "steps_per_s": 630.68, "decode_ms_per_step": 1.59,
     "host_bytes_per_step": 16.0},
    {"mode": "int8-ffip", "decode_chunk": 4, "tok_per_s": 1533.34,
     "steps_per_s": 1471.05, "decode_ms_per_step": 0.68,
     "host_bytes_per_step": 21.3},
]


def _requests(cfg, requests: int, max_new: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 12, requests)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=(int(l),)),
                    max_new_tokens=max_new) for i, l in enumerate(lens)]


def _mix_requests(cfg, requests: int, max_new: int, seed: int, *,
                  long_len: int):
    """Shared-prefix workload + one long prompt: half the requests carry a
    common 16-token prefix (page reuse), the final request is a long prompt
    whose chunked prefill must interleave with the others' decode."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(3, 12, requests)
    base = rng.integers(0, cfg.vocab, size=(16,))
    reqs = []
    for i, l in enumerate(lens):
        p = rng.integers(0, cfg.vocab, size=(int(l),))
        if i % 2 == 0:
            p = np.concatenate([base, p])
        reqs.append(Request(rid=i, prompt=p, max_new_tokens=max_new))
    reqs.append(Request(rid=requests,
                        prompt=rng.integers(0, cfg.vocab, size=(long_len,)),
                        max_new_tokens=max_new))
    return reqs


def bench(arch: str, *, slots: int, requests: int, max_new: int,
          max_len: int, quantized: bool, decode_chunk: int,
          gemm_impl=None, gemm_block=None, seed: int = 0,
          paged: bool = False, page_size: int = 16, prefill_chunk=None,
          paged_attention: str = "gather", mix_long_len: int = 0,
          mesh=None, prepared=None, keep_tokens: bool = False) -> dict:
    cfg = configs.smoke_config(configs.get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # obs_window_s=3600: the windowed TTFT/ITL histograms must cover the
    # whole timed run so nothing expires mid-bench and the windowed
    # percentiles are exact over every steady-state request.
    srv = BatchServer(model, batch_slots=slots, max_len=max_len,
                      quantized=quantized, decode_chunk=decode_chunk,
                      gemm_impl=gemm_impl, gemm_block=gemm_block,
                      paged=paged, page_size=page_size,
                      prefill_chunk=prefill_chunk,
                      paged_attention=paged_attention,
                      mesh=mesh, prepared=prepared,
                      registry=obs.Registry(), obs_window_s=3600.0)

    def _workload(budget, s):
        if mix_long_len:
            return _mix_requests(cfg, requests, budget, s,
                                 long_len=mix_long_len)
        return _requests(cfg, requests, budget, s)

    # --- warmup (untimed region): compile every prompt bucket + the decode
    # program, using the same length distribution as the measured workload.
    # Budget 2: the minimum that reaches a decode dispatch (token 1 comes
    # from prefill), keeping warmup cheap regardless of --max-new. In paged
    # mode this also REGISTERS the prompts' prefix pages, so the timed run
    # measures serving over a warm prefix cache (prefill collapses to the
    # recompute-last-token chunk).
    warm = _workload(2, seed)
    t0 = time.perf_counter()
    for r in warm:
        # request ids are idempotency keys now: the warmup run must not
        # collide with the timed run's rids (same rid => same payload)
        r.rid += 1_000_000
        srv.submit(r)
    srv.run_until_drained(params)
    compile_s = time.perf_counter() - t0

    # fresh registry between warmup and the timed run, so the obs histograms
    # the percentiles come from hold ONLY the steady-state requests
    srv.registry = obs.Registry()
    srv.set_obs_labels(srv.obs_labels)

    # --- timed steady-state run
    reqs = _workload(max_new, seed)
    n_reqs = len(reqs)
    t0 = time.perf_counter()
    for r in reqs:
        srv.submit(r)
    done = srv.run_until_drained(params)
    wall = time.perf_counter() - t0
    assert len(done) == n_reqs, "serve_bench: requests dropped"

    total = sum(len(r.out_tokens) for r in done)
    ttft = [r.t_first - r.t_submit for r in done]
    # e2e percentiles come from the obs latency histogram (exact while the
    # reservoir holds every observation — these workloads are far under it);
    # cross-check against the per-request records so a telemetry regression
    # can never silently skew the bench numbers.
    e2e_hist = srv.registry.get("serve_request_e2e_seconds").labels(
        replica=srv.obs_labels.get("replica", "solo"))
    assert not e2e_hist.overflowed, \
        "e2e reservoir overflowed: percentiles would be partial, not exact"
    e2e = np.array(sorted(r.t_done - r.t_submit for r in done))
    for q, pct in ((0.50, 50), (0.99, 99)):
        assert abs(e2e_hist.quantile(q) - float(np.percentile(e2e, pct))) \
            < 1e-9, "obs e2e histogram diverges from request records"
    # TTFT / inter-token-latency percentiles from the WINDOWED histograms
    # (the same instruments an SLO burns against), cross-checked against the
    # raw per-request lists; the 3600 s window covers the whole timed run.
    w_ttft = srv.registry.get("serve_ttft_window_seconds")
    w_itl = srv.registry.get("serve_itl_window_seconds")
    itl = sorted(v for r in done for v in (r.itl_s or ()))
    for wh, raw in ((w_ttft, sorted(ttft)), (w_itl, itl)):
        assert wh.samples_dropped() == 0, \
            f"{wh.name}: windowed reservoir overflowed during the bench"
        assert wh.count() == len(raw), \
            f"{wh.name}: windowed count {wh.count()} != {len(raw)} raw"
        for q, pct in ((0.50, 50), (0.99, 99)):
            assert abs(wh.quantile(q) - float(np.percentile(raw, pct))) \
                < 1e-9, f"{wh.name} diverges from raw request records"
    st = srv.stats
    steps = st["steps"]
    out = {
        "arch": cfg.name,
        "mode": "int8-ffip" if quantized else "float",
        "gemm": {"impl": gemm_impl or "xla",
                 "block": list(gemm_block) if isinstance(gemm_block, tuple)
                 else gemm_block},
        "slots": slots,
        "requests": n_reqs,
        "decode_chunk": decode_chunk,
        "completed": len(done),
        "tokens_out": total,
        "decode_steps": steps,
        "decode_dispatches": st["decode_dispatches"],
        "compile_s": round(compile_s, 3),
        "wall_s": round(wall, 3),
        "tok_per_s": round(total / wall, 2),
        "steps_per_s": round(steps / max(st["decode_s"], 1e-9), 2),
        "phase_s": {
            "prefill": round(st["prefill_s"], 3),
            "decode": round(st["decode_s"], 3),
            "host_other": round(wall - st["prefill_s"] - st["decode_s"], 3),
        },
        "prefill_tokens": st["prefill_tokens"],
        "prefill_dispatches": st["prefill_dispatches"],
        "decode_tokens": st["decode_tokens"],
        "decode_ms_per_step": round(1e3 * st["decode_s"] / max(steps, 1), 2),
        # queue wait + prefill until the first token, per request; p50/p99
        # sourced from the windowed histogram serve_ttft_window_seconds
        "ttft_ms": {"mean": round(1e3 * sum(ttft) / len(ttft), 2),
                    "max": round(1e3 * max(ttft), 2),
                    "p50": round(1e3 * w_ttft.quantile(0.50), 2),
                    "p99": round(1e3 * w_ttft.quantile(0.99), 2)},
        # per emitted token, from serve_itl_window_seconds (fused decode
        # chunks amortize: each of the k tokens is charged dispatch_dt / k)
        "itl_ms": {"p50": round(1e3 * w_itl.quantile(0.50), 2),
                   "p99": round(1e3 * w_itl.quantile(0.99), 2)},
        # submit -> last token, per request (queue wait included); sourced
        # from the obs histogram serve_request_e2e_seconds
        "e2e_ms": {"p50": round(1e3 * e2e_hist.quantile(0.50), 2),
                   "p99": round(1e3 * e2e_hist.quantile(0.99), 2)},
        # on-device sampling: ids, not logits, cross per decode step
        "host_bytes_per_step": round(st["host_bytes_decode"] / max(steps, 1), 1),
        "host_bytes_per_step_pr2": slots * cfg.vocab * 4,   # (B, V) f32 logits
        "compiles": dict(srv.compiles),
    }
    if paged:
        assert srv._reserved == 0, "page reservation ledger did not drain"
        assert (srv.alloc.free_count + srv.alloc.in_use
                == srv.alloc.num_pages), "page allocator leaked"
        out["tokens_by_rid"] = {r.rid: list(r.out_tokens) for r in done}
        out["paged"] = {
            "attention": paged_attention,
            "page_size": page_size,
            "num_pages": srv.alloc.num_pages,
            "prefill_chunk": srv.prefill_chunk,
            "pages_peak": st["pages_peak"],
            "contiguous_equiv_pages": slots * (max_len // page_size),
            # pages still held by the prefix index after drain (warm cache)
            "prefix_cache_pages_resident": srv.alloc.in_use,
            "prefix_hit_tokens": st["prefix_hit_tokens"],
            "cow_copies": st["cow_copies"],
            "prefill_chunks": st["prefill_chunks"],
            "host_bytes_page_tables": st["host_bytes_page_tables"],
        }
    elif mix_long_len or keep_tokens:
        out["tokens_by_rid"] = {r.rid: list(r.out_tokens) for r in done}
    return out


def bench_prepared(arch: str, *, slots: int, requests: int, max_new: int,
                   max_len: int) -> dict:
    """Cold offline prep vs artifact warm start (the repro.prepare contract):
    time the in-process prep (quantize + Eq. 9 y-deltas), the artifact
    save/load roundtrip, and a warm serve from the loaded artifact with the
    zero-recompute assertion."""
    import shutil
    import tempfile

    from repro import prepare

    cfg = configs.smoke_config(configs.get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    t0 = time.perf_counter()
    pm = prepare.prepare_lm(params, quantized=True)
    # the transforms are lazy jax ops until materialized — block before
    # stopping the clock so cold_prep_s is the real offline cost
    jax.block_until_ready(jax.tree.leaves(pm.params))
    jax.block_until_ready(list(pm.derived.values()))
    cold_prep_s = time.perf_counter() - t0

    tmp = tempfile.mkdtemp(prefix="serve_bench_prep_")
    art_dir = pathlib.Path(tmp) / "artifact"
    try:
        t0 = time.perf_counter()
        pm.save(art_dir)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        pm2 = prepare.load(art_dir)
        jax.block_until_ready(jax.tree.leaves(pm2.params))
        warm_load_s = time.perf_counter() - t0
        nbytes = sum(f.stat().st_size for f in art_dir.iterdir())

        srv = BatchServer(model, batch_slots=slots, max_len=max_len,
                          quantized=True, decode_chunk=4, prepared=pm2)
        for r in _requests(cfg, requests, max_new, 0):
            srv.submit(r)
        done = srv.run_until_drained(params)
        assert len(done) == requests, "serve_bench: requests dropped"
        assert pm2.recomputed == 0, pm2.recompute_report()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    st = srv.stats
    return {
        "arch": cfg.name,
        "cold_prep_s": round(cold_prep_s, 3),
        "save_s": round(save_s, 3),
        "warm_load_s": round(warm_load_s, 3),
        "prep_over_load": round(cold_prep_s / max(warm_load_s, 1e-9), 1),
        "artifact_bytes": nbytes,
        "y_deltas": len(pm.derived),
        "recomputed_after_warm_serve": pm2.recomputed,
        "warm_serve_decode_ms_per_step":
            round(1e3 * st["decode_s"] / max(st["steps"], 1), 2),
    }


def bench_faults(arch: str, *, slots: int, requests: int, max_new: int,
                 max_len: int) -> dict:
    """Fault-tolerance section: 3 replicas, replica 0 flapping on a seeded
    plan (raise/hang alternating, fake clock), vs the same fleet with no
    faults. Records outcome counts, retries/failovers, e2e p50/p99 (fake
    seconds — queue wait + retries dominate, which is the point), and
    asserts every completion is token-identical to the no-fault run."""
    from repro.serve.faults import FakeClock, FaultPlan
    from repro.serve.lifecycle import Lifecycle
    from repro.serve.router import ReplicaRouter, RouterConfig

    cfg = configs.smoke_config(configs.get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(plan):
        reg = obs.Registry()
        servers = [BatchServer(model, batch_slots=slots, max_len=max_len,
                               registry=reg)
                   for _ in range(3)]
        rt = ReplicaRouter(
            servers, params, fault_plan=plan, clock=FakeClock(),
            registry=reg,
            cfg=RouterConfig(step_timeout_s=5.0, quarantine_s=0.2,
                             max_retries=4))
        t0 = time.perf_counter()
        for r in _requests(cfg, requests, max_new, 0):
            rt.submit(r)
        recs = rt.drive(max_ticks=20_000)
        wall = time.perf_counter() - t0
        done = [rec for rec in recs.values()
                if rec.state is Lifecycle.DONE]
        # fake-clock e2e percentiles from the router's obs histogram,
        # cross-checked against the lifecycle records
        hist = reg.get("router_request_e2e_seconds")
        lat = np.array(sorted(rec.t_done - rec.t_submit for rec in done))
        for q, pct in ((0.50, 50), (0.99, 99)):
            assert abs(hist.quantile(q) - float(np.percentile(lat, pct))) \
                < 1e-9, "obs router e2e histogram diverges from records"
        return recs, rt, wall, hist

    quiet_plan = FaultPlan([], seed=0)
    flaky_plan = FaultPlan.flaky_replica(0, start=2, period=4, rounds=4,
                                         seed=0)
    ref, _, quiet_wall, quiet_hist = run(quiet_plan)
    recs, rt, wall, hist = run(flaky_plan)
    for rid, rec in recs.items():
        assert rec.terminal, f"rid {rid} not terminal under faults"
        if rec.state is Lifecycle.DONE:
            assert rec.tokens == ref[rid].tokens, \
                f"rid {rid} diverges from the no-fault fleet"
    return {
        "arch": cfg.name,
        "fleet": {"replicas": 3, "flaky": "replica 0 (raise/hang, "
                                          "4 rounds, period 4)"},
        "plan": json.loads(flaky_plan.to_json()),
        "outcomes": rt.outcome_counts(),
        "router": dict(rt.stats),
        "wall_s": round(wall, 3),
        "wall_s_no_fault": round(quiet_wall, 3),
        # fake-clock seconds: queue wait + backoff + failover, not compute;
        # sourced from the obs histogram router_request_e2e_seconds
        "e2e_fake_s": {
            "no_fault": {"p50": round(quiet_hist.quantile(0.50), 3),
                         "p99": round(quiet_hist.quantile(0.99), 3)},
            "flaky": {"p50": round(hist.quantile(0.50), 3),
                      "p99": round(hist.quantile(0.99), 3)},
        },
        "tokens_identical_to_no_fault": True,
    }


def bench_tp(arch: str, *, slots: int, requests: int, max_new: int,
             max_len: int) -> list:
    """Tensor-parallel decode sweep: ms/step at model-parallel 1/2/4 over
    whatever devices are visible (force host devices with
    XLA_FLAGS=--xla_force_host_platform_device_count=8 to sweep past 1).
    Output tokens are asserted identical across TP widths."""
    from jax.sharding import Mesh

    n = jax.device_count()
    rows, ref_tokens = [], None
    for tp in (1, 2, 4):
        if tp > n:
            continue
        mesh = (Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                     ("data", "model")) if tp > 1 else None)
        for quantized in (False, True):
            r = bench(arch, slots=slots, requests=requests, max_new=max_new,
                      max_len=max_len, quantized=quantized, decode_chunk=1,
                      mesh=mesh, keep_tokens=True)
            toks = r.pop("tokens_by_rid")
            key = r["mode"]
            if tp == 1:
                ref_tokens = ref_tokens or {}
                ref_tokens[key] = toks
            elif ref_tokens and key in ref_tokens:
                assert toks == ref_tokens[key], \
                    f"tp={tp} {key} tokens diverge from single-device"
            rows.append({"tp": tp, "mode": r["mode"],
                         "decode_ms_per_step": r["decode_ms_per_step"],
                         "tok_per_s": r["tok_per_s"],
                         "compile_s": r["compile_s"]})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b",
                    choices=sorted(configs.ARCHS))
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--chunks", type=int, nargs="+", default=[1, 2, 4, 8],
                    help="decode_chunk sweep (quantized mode, being ~5x "
                         "slower, runs only the first value and 4, deduped)")
    ap.add_argument("--gemm-impl", choices=["xla", "pallas"], default=None,
                    help="GEMM provider for the serving forward")
    ap.add_argument("--gemm-block", default=None,
                    help="'auto' = repro.tune schedule cache (tunes flash "
                         "attention blocks too) or explicit 'bm,bn,bk' (needs --gemm-impl pallas)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="paged-section prefill chunk (page-aligned)")
    ap.add_argument("--long-len", type=int, default=48,
                    help="long-prompt length in the paged TTFT mix")
    ap.add_argument("--skip-paged", action="store_true",
                    help="contiguous sweep only")
    ap.add_argument("--skip-prepared", action="store_true",
                    help="skip the prepared-artifact warm-start section")
    ap.add_argument("--skip-tp", action="store_true",
                    help="skip the tensor-parallel decode sweep")
    ap.add_argument("--skip-faults", action="store_true",
                    help="skip the flaky-replica router section")
    args = ap.parse_args()
    gemm_block = args.gemm_block
    if gemm_block and gemm_block != "auto":
        gemm_block = tuple(int(x) for x in gemm_block.split(","))

    results = []
    for quantized in (False, True):
        chunks = args.chunks if not quantized else sorted({args.chunks[0], 4})
        for chunk in chunks:
            results.append(bench(
                args.arch, slots=args.slots, requests=args.requests,
                max_new=args.max_new, max_len=args.max_len,
                quantized=quantized, decode_chunk=chunk,
                gemm_impl=args.gemm_impl, gemm_block=gemm_block))

    def _best(rs, mode):
        return max((r for r in rs if r["mode"] == mode),
                   key=lambda r: r["steps_per_s"])

    # the PR2 baseline was measured on one specific workload; only claim a
    # speedup when this run reproduces it (otherwise skip the comparison
    # rather than divide numbers from different workloads).
    comparable = (args.arch == "minicpm-2b" and args.slots == 4
                  and args.requests == 6 and args.max_new == 4)
    comparison = {}
    for base in BASELINE_PR2 if comparable else []:
        new = _best(results, base["mode"])
        comparison[base["mode"]] = {
            "decode_ms_per_step": {"pr2": base["decode_ms_per_step"],
                                   "now": new["decode_ms_per_step"],
                                   "best_chunk": new["decode_chunk"]},
            "decode_speedup": round(base["decode_ms_per_step"]
                                    / new["decode_ms_per_step"], 2),
            "tok_per_s": {"pr2": base["tok_per_s"], "now": new["tok_per_s"]},
            "host_bytes_per_step": {"pr2": new["host_bytes_per_step_pr2"],
                                    "now": new["host_bytes_per_step"]},
        }

    # --- paged section: shared-prefix + one long prompt, chunked prefill,
    # gather attention (bit-identical math to the contiguous oracle), plus
    # the SAME mix run contiguously so TTFT/footprint sit side by side.
    results_paged, comparison_paged = [], {}
    if not args.skip_paged:
        mix = dict(slots=args.slots, requests=args.requests,
                   max_new=args.max_new, max_len=args.max_len,
                   gemm_impl=args.gemm_impl, gemm_block=gemm_block,
                   mix_long_len=args.long_len)
        for quantized, chunks in ((False, (1, 4)), (True, (4,))):
            for chunk in chunks:
                results_paged.append(bench(
                    args.arch, quantized=quantized, decode_chunk=chunk,
                    paged=True, page_size=args.page_size,
                    prefill_chunk=args.prefill_chunk, **mix))
        ref = bench(args.arch, quantized=False, decode_chunk=4, **mix)
        pg = next(r for r in results_paged
                  if r["mode"] == "float" and r["decode_chunk"] == 4)
        assert pg.pop("tokens_by_rid") == ref.pop("tokens_by_rid"), \
            "paged gather outputs diverge from contiguous on the mix workload"
        for r in results_paged:
            r.pop("tokens_by_rid", None)
        comparison_paged = {
            "workload": (f"{args.requests} shared-prefix requests + one "
                         f"{args.long_len}-token prompt, prefill_chunk="
                         f"{args.prefill_chunk} (chunks interleave with "
                         "decode), warm prefix cache, outputs byte-identical"),
            "ttft_ms": {"contiguous": ref["ttft_ms"],
                        "paged_chunked": pg["ttft_ms"]},
            "prefill_tokens": {"contiguous": ref["prefill_tokens"],
                               "paged_warm_prefix": pg["prefill_tokens"]},
            "pages_peak": pg["paged"]["pages_peak"],
            "contiguous_equiv_pages": pg["paged"]["contiguous_equiv_pages"],
            "prefix_hit_tokens": pg["paged"]["prefix_hit_tokens"],
        }

    # --- prepared-artifact warm start + tensor-parallel decode sections
    results_prepared = {} if args.skip_prepared else bench_prepared(
        args.arch, slots=args.slots, requests=args.requests,
        max_new=args.max_new, max_len=args.max_len)
    results_tp = [] if args.skip_tp else bench_tp(
        args.arch, slots=args.slots, requests=args.requests,
        max_new=args.max_new, max_len=args.max_len)
    results_faults = {} if args.skip_faults else bench_faults(
        args.arch, slots=args.slots, requests=args.requests,
        max_new=args.max_new, max_len=args.max_len)

    out = {
        "bench": "serve",
        "note": ("CPU-only container: interpret-mode timings; ratios, the "
                 "chunk sweep, and the host-transfer reduction are the "
                 "load-bearing numbers. compile_s is jit warmup, excluded "
                 "from wall_s (baseline_pr2 wall_s includes it). "
                 "baseline_prev = contiguous numbers from just before the "
                 "block-paged KV cache landed. Paged rows time the GATHER "
                 "oracle + per-chunk host dispatch on CPU (worst case for "
                 "paging); the load-bearing paged outputs are the footprint "
                 "(pages_peak vs contiguous_equiv_pages) and the "
                 "prefix-hit / prefill-token collapse, not tok/s. "
                 "e2e_ms / e2e_fake_s percentiles are sourced from the "
                 "repro.obs latency histograms (exact-reservoir quantiles, "
                 "cross-checked against per-request records in-process)."),
        "baseline_pr2": BASELINE_PR2,
        "baseline_prev": BASELINE_PREV,
        "comparison": comparison,
        "comparison_paged": comparison_paged,
        "results": results,
        "results_paged": results_paged,
        # repro.prepare warm start: cold offline prep vs artifact load, plus
        # a warm serve with the zero-recompute assertion
        "results_prepared": results_prepared,
        # tensor-parallel decode ms/step at model-parallel 1/2/4 (widths
        # beyond the visible device count are skipped; tokens asserted
        # identical across widths)
        "results_tp": results_tp,
        # multi-replica router with 1-of-3 replicas flapping on a seeded
        # plan: outcome counts, retries/failovers, and the e2e latency tax
        # of failover vs the no-fault fleet (completions token-identical)
        "results_faults": results_faults,
    }
    OUT.write_text(json.dumps(out, indent=2) + "\n")
    for r in results:
        print(f"serve_bench.{r['arch']}.{r['mode']}.chunk{r['decode_chunk']},"
              f"{r['tok_per_s']} tok/s,{r['steps_per_s']} steps/s,"
              f"decode={r['phase_s']['decode']}s,"
              f"ttft_p99={r['ttft_ms']['p99']}ms,"
              f"itl_p99={r['itl_ms']['p99']}ms,"
              f"compile={r['compile_s']}s,"
              f"host_B/step={r['host_bytes_per_step']}")
    for r in results_paged:
        p = r["paged"]
        print(f"serve_bench.{r['arch']}.{r['mode']}.paged-chunk"
              f"{r['decode_chunk']},{r['tok_per_s']} tok/s,"
              f"ttft_mean={r['ttft_ms']['mean']}ms,"
              f"pages_peak={p['pages_peak']}/{p['contiguous_equiv_pages']},"
              f"prefix_hit={p['prefix_hit_tokens']} tok,"
              f"cow={p['cow_copies']},chunks={p['prefill_chunks']}")
    for mode, c in comparison.items():
        print(f"vs PR2 [{mode}]: decode {c['decode_ms_per_step']['pr2']}ms -> "
              f"{c['decode_ms_per_step']['now']}ms/step "
              f"({c['decode_speedup']}x), host bytes/step "
              f"{c['host_bytes_per_step']['pr2']} -> "
              f"{c['host_bytes_per_step']['now']}")
    if comparison_paged:
        c = comparison_paged
        print(f"paged mix: ttft mean {c['ttft_ms']['contiguous']['mean']}ms "
              f"(contiguous) vs {c['ttft_ms']['paged_chunked']['mean']}ms "
              f"(paged+chunked, warm prefix), prefill tokens "
              f"{c['prefill_tokens']['contiguous']} -> "
              f"{c['prefill_tokens']['paged_warm_prefix']}, pages_peak "
              f"{c['pages_peak']}/{c['contiguous_equiv_pages']}")
    if results_prepared:
        p = results_prepared
        print(f"prepared: cold prep {p['cold_prep_s']}s vs warm load "
              f"{p['warm_load_s']}s ({p['prep_over_load']}x), "
              f"{p['y_deltas']} y-deltas, {p['artifact_bytes']} B, "
              f"recomputed={p['recomputed_after_warm_serve']}")
    for r in results_tp:
        print(f"serve_bench.tp{r['tp']}.{r['mode']},"
              f"decode_ms_per_step={r['decode_ms_per_step']},"
              f"{r['tok_per_s']} tok/s")
    if results_faults:
        f = results_faults
        print(f"faults: outcomes={f['outcomes']}, "
              f"retries={f['router']['retries']}, "
              f"failures={f['router']['replica_failures']}, "
              f"quarantines={f['router']['quarantines']}, "
              f"e2e p99 {f['e2e_fake_s']['no_fault']['p99']} -> "
              f"{f['e2e_fake_s']['flaky']['p99']} fake-s, "
              f"tokens identical: {f['tokens_identical_to_no_fault']}")
    print(f"wrote {OUT}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
