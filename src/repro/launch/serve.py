"""Serving launcher: per-slot continuous batching over any arch.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --requests 8 --slots 4 --decode-chunk 4

``--quantized`` routes the dense/attention projections through the int8 FFIP
decode path (offline-quantized weights, Eq. 15 folded beta, Eq. 20 zero-point
adjuster). ``--decode-chunk N`` fuses N decode steps into one dispatch
(sampling stays on device either way); bucketed batched prefill is on by
default (``--no-prefill-buckets`` forces the per-slot fallback).
``--gemm-impl pallas`` routes the serving projections through the Pallas
kernels and ``--gemm-block auto`` resolves their block shapes (plus flash
attention's) from the ``repro.tune`` schedule cache — pre-populate it with
``python -m repro.launch.tune``.

``--paged`` switches to the block-paged KV cache (page pool + per-slot page
tables, refcounted prefix sharing, chunked prefill); ``--shared-prefix``
makes the synthetic workload share a long prompt prefix so page reuse has
something to bite on, and ``--compare-contiguous`` re-runs the identical
workload on the contiguous cache and asserts BYTE-IDENTICAL outputs plus a
paged-footprint win. Exits non-zero if any request is dropped or over/under-
generates, so this doubles as the CI batcher-regression smoke.

``--replicas N`` serves the workload through the fault-tolerant
multi-replica router instead of a single server: N data-parallel
``BatchServer`` replicas (``--quantized-replicas M`` makes the last M of
them int8-FFIP shed targets) behind load-aware dispatch, bounded-queue
admission control, per-request deadlines (``--deadline-ms``), bounded
retries and a per-replica circuit breaker. ``--fault-plan`` installs a
deterministic chaos schedule — inline JSON, ``@path/to/plan.json``, or the
shorthand ``flaky`` (replica 0 flaps raise/hang) — driven on a fake clock;
the run must end with every request DONE (token-identical to a no-fault
oracle of its serving tier) or failed with a TYPED error, never stuck.

Observability (``repro.obs``): ``--metrics-json PATH`` dumps the run's
metric registry snapshot (plus the unified compile-counter snapshot) as
JSON at exit; ``--trace-out PATH`` writes the span trace — ``.jsonl`` for
the line-per-span form, anything else for Chrome ``trace_event`` JSON
(load in Perfetto / chrome://tracing); ``--metrics-port N`` serves live
Prometheus text on ``http://127.0.0.1:N/metrics`` for the duration of the
run. ``python -m repro.launch.obs_check`` validates the two files against
the workload (CI obs-smoke gate).

``--prepared DIR`` serves from a `repro.prepare` artifact (built with
``python -m repro.launch.prepare``) instead of preparing weights in-process:
warm start, zero re-quantization / y re-encode / re-tune. ``--mesh-model N``
runs tensor-parallel decode over the first N devices (the repro.dist rule
engine shards params + KV cache on the "model" axis) and
``--compare-single-device`` re-runs the workload without the mesh and asserts
byte-identical output tokens. ``--require-warm`` fails fast — listing the
missing keys — if any schedule-cache lookup missed or the artifact had to
recompute anything.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import numpy as np

import repro.obs as obs
from repro import configs
from repro.models.model import build_model
from repro.obs import profile as obs_profile
from repro.serve.batcher import BatchServer, Request


def _make_prompts(cfg, n_requests, shared_prefix, rng):
    lens = rng.integers(3, 12, n_requests)
    if not shared_prefix:
        return [rng.integers(0, cfg.vocab, size=(int(lens[i]),))
                for i in range(n_requests)]
    # half the requests carry a common 16-token prefix; one is an exact
    # duplicate of another (whole-prompt hit including the partial tail page)
    base = rng.integers(0, cfg.vocab, size=(16,))
    prompts = []
    for i in range(n_requests):
        if i % 2 == 0:
            tail = rng.integers(0, cfg.vocab, size=(int(lens[i]),))
            prompts.append(np.concatenate([base, tail]))
        else:
            prompts.append(rng.integers(0, cfg.vocab, size=(int(lens[i]),)))
    if n_requests >= 3:
        prompts[-1] = prompts[0].copy()
    return prompts


def _make_mesh(tp: int):
    from jax.sharding import Mesh
    n = len(jax.devices())
    if tp > n:
        raise SystemExit(f"--mesh-model {tp} but only {n} devices visible "
                         f"(XLA_FLAGS=--xla_force_host_platform_device_count="
                         f"{tp} forces host devices)")
    return Mesh(np.array(jax.devices()[:tp]).reshape(1, tp),
                ("data", "model"))


def _serve(model, params, prompts, max_new, args, *, paged, mesh=None,
           prepared=None):
    srv = BatchServer(
        model, batch_slots=args.slots, max_len=args.max_len,
        quantized=args.quantized, decode_chunk=args.decode_chunk,
        gemm_impl=args.gemm_impl, gemm_block=args.gemm_block_parsed,
        prefill_buckets=not args.no_prefill_buckets, paged=paged,
        page_size=args.page_size, num_pages=args.num_pages,
        prefill_chunk=args.prefill_chunk,
        paged_attention=args.paged_attention, mesh=mesh, prepared=prepared)
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=p, max_new_tokens=max_new))
    done = srv.run_until_drained(params)
    return srv, done, time.perf_counter() - t0


def _serve_router(model, params, prompts, args, *, mesh=None, prepared=None):
    """Multi-replica serving path (--replicas): returns exit-gate failures."""
    from repro.serve.faults import FakeClock, FaultPlan
    from repro.serve.lifecycle import Lifecycle, ServeStallError
    from repro.serve.router import ReplicaRouter, RouterConfig

    plan = None
    if args.fault_plan:
        plan = (FaultPlan.flaky_replica(0) if args.fault_plan == "flaky"
                else FaultPlan.parse(args.fault_plan))
    nq = min(args.quantized_replicas, args.replicas)
    tiers = [i >= args.replicas - nq for i in range(args.replicas)]
    clock = FakeClock() if plan is not None else None

    objectives = None
    if args.slo:
        fast_s, slow_s = (float(x) for x in args.slo_windows.split(","))
        objectives = [obs.Objective.parse(
            spec, fast_window_s=fast_s, slow_window_s=slow_s,
            min_count=args.slo_min_count) for spec in args.slo]

    def mk(q):
        # clock threading: under a fault plan every replica reads the SAME
        # fake clock as the router, so spans/latency histograms line up with
        # the deterministic fault schedule.
        return BatchServer(
            model, batch_slots=args.slots, max_len=args.max_len,
            quantized=q, decode_chunk=args.decode_chunk,
            gemm_impl=args.gemm_impl, gemm_block=args.gemm_block_parsed,
            prefill_buckets=not args.no_prefill_buckets, paged=args.paged,
            page_size=args.page_size, num_pages=args.num_pages,
            prefill_chunk=args.prefill_chunk,
            paged_attention=args.paged_attention, mesh=mesh,
            prepared=prepared, clock=clock)

    servers = [mk(q or args.quantized) for q in tiers]
    rt = ReplicaRouter(servers, params, fault_plan=plan, clock=clock,
                       cfg=RouterConfig(
                           step_timeout_s=5.0, quarantine_s=0.2,
                           max_retries=4, objectives=objectives,
                           default_deadline_s=(args.deadline_ms / 1000.0
                                               if args.deadline_ms else
                                               None)))
    t0 = time.perf_counter()
    for i, p in enumerate(prompts):
        rt.submit(Request(rid=i, prompt=p, max_new_tokens=args.max_new,
                          eos_id=-1))
    try:
        recs = rt.drive(max_ticks=50_000)
    except ServeStallError as e:
        raise SystemExit(f"FAIL: {e}")
    # extra idle ticks so the burn windows can expire and the degradation
    # controller can walk back to healthy (the obs_check recovery gate)
    for _ in range(args.slo_drain_ticks):
        rt.step()
    dt = time.perf_counter() - t0

    # no-fault single-server oracle per tier that actually served work
    want = {}
    for q in sorted({rec.tier == "int8" for rec in recs.values()
                     if rec.state is Lifecycle.DONE}):
        ref = mk(q)
        for i, p in enumerate(prompts):
            ref.submit(Request(rid=i, prompt=p, max_new_tokens=args.max_new,
                               eos_id=-1))
        want[q] = {r.rid: list(r.out_tokens)
                   for r in ref.run_until_drained(params)}

    outcomes = rt.outcome_counts()
    done = [rec for rec in recs.values() if rec.state is Lifecycle.DONE]
    lat = np.array(sorted(rec.t_done - rec.t_submit for rec in done)) \
        if done else np.zeros((0,))
    unit = "fake-s" if clock is not None else "s"
    mode = (f"router x{args.replicas}"
            + (f" ({nq} int8 shed targets)" if nq else "")
            + ("/paged" if args.paged else "")
            + (f"/faults[{len(plan.faults)}]" if plan is not None else ""))
    print(f"[{mode}] {len(done)}/{len(prompts)} done in {dt:.2f}s wall — "
          f"outcomes {outcomes}")
    if len(lat):
        print(f"  e2e latency ({unit}): p50={np.percentile(lat, 50):.4f} "
              f"p99={np.percentile(lat, 99):.4f}")
    print(f"  router: {rt.stats}")
    if rt.slo is not None:
        states = {k: v.name for k, v in rt.slo.states().items()}
        ctl = {key[0]: int(c.value) for key, c in
               rt.registry.get("router_controller_total")._children.items()}
        print(f"  slo: states={states} controller={rt.ctl_state} "
              f"actions={ctl}")

    problems = []
    if any(not rec.terminal for rec in recs.values()):
        problems.append("non-terminal requests after drive()")
    for rec in recs.values():
        if rec.state is Lifecycle.DONE:
            if rec.tokens != want[rec.tier == "int8"][rec.req.rid]:
                problems.append(
                    f"rid {rec.req.rid}: tokens diverge from the no-fault "
                    f"{rec.tier} oracle")
        elif rec.error is None:
            problems.append(f"rid {rec.req.rid}: failed without a typed "
                            f"error ({rec.state.value})")
    if plan is None and args.deadline_ms is None and len(done) != len(recs):
        problems.append("requests failed with no faults injected")
    for s in servers:
        if s.paged and s._reserved != 0:
            problems.append("page reservation ledger did not drain to 0")
    return problems, rt


def _write_obs(args, tracer) -> None:
    """Dump --metrics-json / --trace-out. Called BEFORE the regression gates
    raise, so a failing run still leaves its telemetry behind for triage."""
    if args.metrics_json:
        import json
        payload = {"metrics": obs.get_registry().snapshot(),
                   "compile": obs_profile.compile_snapshot()}
        with open(args.metrics_json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"  obs: metrics -> {args.metrics_json}")
    if args.trace_out and tracer is not None:
        tracer.write(args.trace_out)
        print(f"  obs: trace ({len(tracer.spans)} spans, "
              f"{tracer.dropped} dropped) -> {args.trace_out}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--quantized", action="store_true",
                    help="int8 FFIP decode path (offline weight quantization)")
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="decode steps fused into one dispatch (lax.scan)")
    ap.add_argument("--no-prefill-buckets", action="store_true",
                    help="disable bucketed batched prefill (per-slot fallback)")
    ap.add_argument("--gemm-impl", choices=["xla", "pallas"], default=None,
                    help="GEMM provider for the serving forward "
                         "(pallas = the paper's kernels)")
    ap.add_argument("--gemm-block", default=None,
                    help="'auto' (repro.tune schedule cache; also tunes flash "
                         "attention blocks) or explicit 'bm,bn,bk' (needs --gemm-impl pallas)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache (page pool + page tables, "
                         "prefix sharing, chunked prefill)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool size; default slots * max_len / page_size")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="page-aligned prefill chunk width; default max_len "
                         "(one chunk per prompt)")
    ap.add_argument("--paged-attention", choices=["gather", "flash"],
                    default="gather",
                    help="gather = contiguous-view oracle math (bit-identical "
                         "to --no --paged); flash = paged Pallas kernel")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="workload shares a 16-token prefix across half the "
                         "requests + one exact duplicate prompt")
    ap.add_argument("--compare-contiguous", action="store_true",
                    help="also run the contiguous cache on the same workload "
                         "and assert byte-identical outputs (needs --paged)")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="serve through the multi-replica router over N "
                         "data-parallel BatchServer replicas (0 = single "
                         "server, the default)")
    ap.add_argument("--quantized-replicas", type=int, default=0, metavar="M",
                    help="make the last M of --replicas int8-FFIP shed "
                         "targets (graceful degradation under pressure)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request end-to-end deadline for the router "
                         "path (typed TIMED_OUT past it)")
    ap.add_argument("--fault-plan", default=None, metavar="JSON|@FILE|flaky",
                    help="deterministic chaos schedule for the router path "
                         "(inline JSON, @path, or 'flaky'); runs on a fake "
                         "clock")
    ap.add_argument("--slo", action="append", default=None, metavar="SPEC",
                    help="SLO objective for the router path, repeatable — "
                         "'ttft_ms p99 < 2000' or 'error_rate < 0.25'; "
                         "enables the burn-rate degradation controller")
    ap.add_argument("--slo-windows", default="5,30", metavar="FAST,SLOW",
                    help="burn-rate window lengths in (fake) seconds "
                         "(default 5,30)")
    ap.add_argument("--slo-min-count", type=int, default=3,
                    help="min samples per window before an SLO can PAGE")
    ap.add_argument("--slo-drain-ticks", type=int, default=0, metavar="N",
                    help="idle router ticks after the workload drains, so "
                         "burn windows expire and the controller recovers")
    ap.add_argument("--prepared", default=None, metavar="DIR",
                    help="serve from a repro.prepare artifact "
                         "(python -m repro.launch.prepare)")
    ap.add_argument("--mesh-model", type=int, default=0, metavar="N",
                    help="tensor-parallel decode over the first N devices "
                         "(repro.dist sharding on the 'model' axis)")
    ap.add_argument("--compare-single-device", action="store_true",
                    help="re-run the workload without the mesh and assert "
                         "byte-identical output tokens (needs --mesh-model)")
    ap.add_argument("--require-warm", action="store_true",
                    help="fail fast (listing missing keys) if any schedule "
                         "lookup missed or the prepared artifact recomputed "
                         "offline work")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the repro.obs metric-registry snapshot (+ "
                         "unified compile counters) as JSON at exit")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the span trace: *.jsonl = one span per line, "
                         "otherwise Chrome trace_event JSON (Perfetto)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="N",
                    help="serve live Prometheus text on 127.0.0.1:N/metrics "
                         "for the duration of the run (0 = ephemeral port)")
    args = ap.parse_args()
    if args.slo and not args.replicas:
        raise SystemExit("--slo requires --replicas (the burn-rate "
                         "degradation controller lives in the router)")
    args.gemm_block_parsed = args.gemm_block
    if args.gemm_block and args.gemm_block != "auto":
        args.gemm_block_parsed = tuple(
            int(x) for x in args.gemm_block.split(","))

    # Fresh per-run registry + profiler so --metrics-json captures exactly
    # this run (servers/routers/kernel hooks all resolve the process default
    # at construction time).
    obs.set_registry(obs.Registry())
    obs_profile.set_profiler(None)
    if args.metrics_port is not None:
        httpd = obs.start_metrics_server(obs.get_registry(),
                                         port=args.metrics_port)
        print(f"metrics: http://{httpd.server_address[0]}:"
              f"{httpd.server_address[1]}/metrics")

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = configs.smoke_config(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    prepared = None
    if args.prepared:
        from repro import prepare
        from repro.prepare.artifact import ArtifactError
        t0 = time.perf_counter()
        try:
            prepared = prepare.load(args.prepared)
            print(f"loaded prepared artifact {args.prepared} "
                  f"({len(prepared.derived)} y-deltas, "
                  f"{len(prepared.schedule)} schedule entries, "
                  f"{time.perf_counter() - t0:.2f}s)")
        except ArtifactError as e:
            # graceful degradation: a corrupt artifact (already quarantined
            # by the loader) falls back to in-process preparation instead of
            # taking serving down — unless warm start was REQUIRED.
            if args.require_warm:
                raise SystemExit(f"--require-warm but the prepared artifact "
                                 f"is unusable: {e}")
            print(f"WARNING: prepared artifact unusable ({e}); falling back "
                  f"to in-process preparation", file=sys.stderr)
    mesh = _make_mesh(args.mesh_model) if args.mesh_model else None
    if args.require_warm:
        from repro import tune
        tune.reset_stats()

    rng = np.random.default_rng(0)
    prompts = _make_prompts(cfg, args.requests, args.shared_prefix, rng)

    if args.replicas:
        problems, rt = _serve_router(model, params, prompts, args, mesh=mesh,
                                     prepared=prepared)
        _write_obs(args, rt.tracer)
        if problems:
            print("FAIL:\n  " + "\n  ".join(problems), file=sys.stderr)
            raise SystemExit(1)
        print("OK")
        return

    srv, done, dt = _serve(model, params, prompts, args.max_new, args,
                           paged=args.paged, mesh=mesh, prepared=prepared)
    _write_obs(args, srv.tracer)

    total = sum(len(r.out_tokens) for r in done)
    mode = "int8-ffip" if args.quantized else "float"
    if args.paged:
        mode += f"/paged-{args.paged_attention}"
    if mesh is not None:
        mode += f"/tp{args.mesh_model}"
    if prepared is not None:
        mode += "/prepared"
    st = srv.stats
    print(f"[{mode}] {len(done)}/{args.requests} requests / {total} tokens "
          f"in {dt:.2f}s ({total / dt:.1f} tok/s host-side, "
          f"decode_chunk={args.decode_chunk})")
    print(f"  prefill {st['prefill_s']:.2f}s ({st['prefill_tokens']} tok / "
          f"{st['prefill_dispatches']} dispatches), "
          f"decode {st['decode_s']:.2f}s over {st['steps']} steps / "
          f"{st['decode_dispatches']} dispatches ({st['decode_tokens']} tok), "
          f"host/other {dt - st['prefill_s'] - st['decode_s']:.2f}s")
    print(f"  compiles: prefill={srv.compiles['prefill']} "
          f"decode={srv.compiles['decode']}, "
          f"host transfer {st['host_bytes_prefill'] + st['host_bytes_decode']}"
          f" B total "
          f"(sampling on device: ids only, never (B, V) logits)")
    if args.paged:
        cap = srv.b * srv.max_pages
        print(f"  paged: pages_peak={st['pages_peak']}/{srv.alloc.num_pages} "
              f"(contiguous equivalent {cap}), "
              f"prefix_hit_tokens={st['prefix_hit_tokens']}, "
              f"cow_copies={st['cow_copies']}, "
              f"prefill_chunks={st['prefill_chunks']}, "
              f"page-table upload {st['host_bytes_page_tables']} B")
    if args.gemm_block == "auto":
        from repro import tune
        print(f"  tune: {tune.stats['hits']} schedule hits / "
              f"{tune.stats['misses']} misses (cache: "
              f"{tune.get_cache().path})")

    # regression gates: nothing dropped, exact token budgets, valid ids
    assert len(done) == args.requests, "run_until_drained dropped requests"
    assert sorted(r.rid for r in done) == list(range(args.requests))
    for r in done:
        assert len(r.out_tokens) == r.max_new_tokens, \
            (r.rid, len(r.out_tokens), r.max_new_tokens)
        assert all(0 <= t < cfg.vocab for t in r.out_tokens), r.rid
    if args.paged:
        assert srv._reserved == 0, "page reservation ledger did not drain"
        assert (srv.alloc.free_count + srv.alloc.in_use
                == srv.alloc.num_pages), "page allocator leaked"
        if args.shared_prefix:
            assert st["prefix_hit_tokens"] > 0, "no prefix reuse observed"
            assert st["pages_peak"] < srv.b * srv.max_pages, \
                "paged footprint should beat slots x max_len under sharing"
    if args.compare_contiguous:
        if not args.paged:
            raise SystemExit("--compare-contiguous requires --paged")
        ref_srv, ref_done, _ = _serve(model, params, prompts, args.max_new,
                                      args, paged=False)
        got = {r.rid: r.out_tokens for r in done}
        want = {r.rid: r.out_tokens for r in ref_done}
        assert got == want, "paged outputs diverge from contiguous oracle"
        print(f"  compare-contiguous: {total} tokens byte-identical")
    if args.compare_single_device:
        if mesh is None:
            raise SystemExit("--compare-single-device requires --mesh-model")
        ref_srv, ref_done, _ = _serve(model, params, prompts, args.max_new,
                                      args, paged=args.paged, mesh=None,
                                      prepared=prepared)
        got = {r.rid: r.out_tokens for r in done}
        want = {r.rid: r.out_tokens for r in ref_done}
        assert got == want, \
            f"tp{args.mesh_model} tokens diverge from single-device"
        print(f"  compare-single-device: {total} tokens byte-identical "
              f"at tp={args.mesh_model}")
    if args.require_warm:
        from repro import tune
        problems = []
        if tune.stats["misses"]:
            problems.append(
                f"{tune.stats['misses']} schedule-cache misses fell back to "
                f"defaults:\n    " + "\n    ".join(sorted(tune._warned_keys)))
        if prepared is not None and prepared.recomputed:
            problems.append(
                f"prepared artifact recomputed offline work: "
                f"{prepared.recompute_report()}")
        if problems:
            print("--require-warm: FAIL\n  " + "\n  ".join(problems),
                  file=sys.stderr)
            raise SystemExit(1)
        checks = ["0 schedule misses"]
        if prepared is not None:
            checks.append("prepared.recomputed == 0")
        print(f"  require-warm: {', '.join(checks)}")
    print("OK")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
