"""The repro.obs telemetry subsystem (metrics / trace / profile) and the
contracts it enforces across the serving stack:

* metrics layer: bucket-boundary (``le``) correctness, exact-reservoir
  quantiles, the label-cardinality guard (per-request ids are REJECTED),
  Prometheus text round-trip, snapshot determinism under FakeClock, and the
  scrape endpoint;
* trace layer: ring-buffer bounding, span-tree reconstruction (including
  a retried + fault-injected request across two replicas), JSONL/Chrome
  export round-trip;
* profiler: FIP/FFIP multiplier accounting (Eqs. 1/5/7), the eager-dispatch
  vs compile-trace split at the real kernel call site;
* serving integration satellites: BatchServer clock injection, the
  ``_fresh_stats`` per-drain reset contract, the bounded span ring,
  and the train-watchdog shim that must never re-grow its own bookkeeping.
"""
import dataclasses
import inspect
import json

import jax
import numpy as np
import pytest

import repro.obs as obs
from repro import configs
from repro.models.model import build_model
from repro.obs import (CardinalityError, Registry, Tracer, load_jsonl,
                       parse_prometheus, start_metrics_server,
                       tree_from_spans)
from repro.obs import profile as obs_profile
from repro.serve.batcher import BatchServer, Request
from repro.serve.faults import FakeClock, FaultPlan, FaultSpec
from repro.serve.lifecycle import Lifecycle
from repro.serve.router import ReplicaRouter, RouterConfig
from repro.watchdog import HangError, Watchdog, WatchdogConfig
from repro.train.watchdog import StepWatchdog

MAX_LEN = 48
MAX_NEW = 4
LENS = [3, 7, 5]

_STATE = {}


def _setup():
    if not _STATE:
        cfg = configs.smoke_config(configs.get_config("minicpm-2b"))
        cfg = dataclasses.replace(cfg, attention_impl="naive")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _STATE["m"] = (cfg, model, params)
    return _STATE["m"]


def _prompts(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, size=(n,)) for n in LENS]


# -- metrics layer -----------------------------------------------------------

def test_histogram_bucket_boundaries():
    """le semantics: a value EQUAL to a bound lands in that bound's bucket;
    export is cumulative."""
    r = Registry()
    h = r.histogram("lat_s", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 2.0, 2.5):
        h.observe(v)
    snap = r.snapshot()["lat_s"]["series"][0]
    assert snap["count"] == 4 and snap["sum"] == pytest.approx(6.0)
    by_le = {b["le"]: b["count"] for b in snap["buckets"]}
    assert by_le == {1.0: 2, 2.0: 3, "+Inf": 4}


def test_histogram_quantile_exact_then_interpolated():
    r = Registry()
    h = r.histogram("q_s", buckets=(1.0, 2.0, 4.0), reservoir=100)
    vals = [0.1 * i for i in range(1, 42)]
    for v in vals:
        h.observe(v)
    for q in (0.0, 0.25, 0.5, 0.99, 1.0):
        assert h.quantile(q) == pytest.approx(
            float(np.percentile(vals, 100 * q)))
    # past the reservoir the quantile degrades to bucket interpolation but
    # must stay inside the containing bucket
    tiny = r.histogram("tiny_s", buckets=(1.0, 2.0, 4.0), reservoir=4)
    for v in (0.5, 1.5, 1.6, 3.0, 3.5):
        tiny.observe(v)
    assert 2.0 <= tiny.quantile(0.9) <= 4.0


def test_label_cardinality_guard():
    r = Registry()
    for bad in ("rid", "request_id", "req_id"):
        with pytest.raises(CardinalityError):
            r.counter(f"x_{bad}_total", "t", (bad,))
    c = r.counter("caps_total", "t", ("k",))
    for i in range(c.max_label_sets):
        c.labels(k=str(i)).inc()
    with pytest.raises(CardinalityError):
        c.labels(k="one-too-many")


def test_unbound_labeled_family_rejects_observations():
    r = Registry()
    with pytest.raises(ValueError, match="bind with .labels"):
        r.counter("fam_total", "t", ("phase",)).inc()


def test_registry_idempotent_reregistration():
    r = Registry()
    assert r.counter("same_total", "t") is r.counter("same_total", "t")
    with pytest.raises(ValueError):
        r.gauge("same_total")


def test_prometheus_round_trip():
    r = Registry()
    r.counter("req_total", "requests", ("replica",)).labels(replica="0").inc(3)
    r.gauge("depth").set(2.5)
    h = r.histogram("lat_s", "latency", ("phase",), buckets=(0.01, 0.1))
    h.labels(phase="decode").observe(0.01)
    h.labels(phase="decode").observe(0.5)
    parsed = parse_prometheus(r.to_prometheus())
    assert parsed["req_total"][(("replica", "0"),)] == 3.0
    assert parsed["depth"][()] == 2.5
    dec = (("phase", "decode"),)
    assert parsed["lat_s_count"][dec] == 2.0
    assert parsed["lat_s_sum"][dec] == pytest.approx(0.51)
    assert parsed["lat_s_bucket"][(("phase", "decode"), ("le", "0.01"))] == 1.0
    assert parsed["lat_s_bucket"][(("phase", "decode"), ("le", "+Inf"))] == 2.0


def test_snapshot_deterministic_under_fake_clock():
    """Byte-identical snapshots from identical FakeClock-timed runs — the
    metrics layer itself never reads a clock."""
    def build():
        clock = FakeClock()
        r = Registry()
        t = Tracer(clock=clock)
        h = r.histogram("work_s", buckets=(0.1, 1.0))
        for i in range(5):
            s = t.start("step", rid=str(i % 2))
            clock.advance(0.05 * (i + 1))
            t.end(s)
            h.observe(s.duration)
            r.counter("steps_total").inc()
        return json.dumps(r.snapshot(), sort_keys=True), t.to_jsonl()
    assert build() == build()


def test_metrics_http_endpoint_scrapes():
    import urllib.request
    r = Registry()
    r.counter("scrape_total").inc(7)
    srv = start_metrics_server(r, port=0)
    try:
        port = srv.server_address[1]
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics").read().decode()
        assert parse_prometheus(txt)["scrape_total"][()] == 7.0
        blob = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json").read()
        assert json.loads(blob)["scrape_total"]["series"][0]["value"] == 7.0
    finally:
        srv.shutdown()


# -- trace layer -------------------------------------------------------------

def test_tracer_ring_bounded():
    t = Tracer(clock=FakeClock(), capacity=8)
    for i in range(20):
        t.end(t.start("s", rid=str(i)))
    assert len(t.spans) == 8
    assert t.dropped == 12


def test_span_tree_and_export_round_trip(tmp_path):
    clock = FakeClock()
    t = Tracer(clock=clock)
    root = t.start("request", rid="7")
    a = t.start("queued", parent=root.sid, rid="7")
    clock.advance(0.01)
    t.end(a)
    b = t.start("decoding", parent=root.sid, rid="7")
    clock.advance(0.02)
    t.end(b)
    t.end(root, outcome="done")

    tree = t.span_tree("7")
    assert tree["name"] == "request" and tree["attrs"]["outcome"] == "done"
    assert [c["name"] for c in tree["children"]] == ["queued", "decoding"]

    p = tmp_path / "trace.jsonl"
    t.write(str(p))
    assert tree_from_spans(load_jsonl(str(p)), "7") == tree

    chrome = t.to_chrome_trace()
    names = {e["name"] for e in chrome["traceEvents"]}
    assert {"request", "queued", "decoding", "thread_name"} <= names


# -- profiler ----------------------------------------------------------------

def test_profiler_fip_multiplier_accounting():
    """Eq. 1 effective ops; Eqs. 5/7 multiplier counts (FIP/FFIP halve the
    multiplies for even K; baseline and odd-K fall back to m*k*n)."""
    r = Registry()
    p = obs_profile.KernelProfiler(r)
    p.record_gemm(16, 8, 12, algo="ffip", dtype="float32")
    p.record_gemm(16, 8, 12, algo="baseline", dtype="float32")
    def mults(algo):
        return r.get("repro_kernel_mults_total").labels(
            kernel="gemm", algo=algo, dtype="float32").value
    assert r.get("repro_kernel_flops_total").labels(
        kernel="gemm", algo="ffip", dtype="float32").value == 2880.0
    assert mults("ffip") == 880.0          # (mkn + mk + nk) / 2
    assert mults("baseline") == 1536.0     # mkn
    # traced calls count compilations, not work
    p.record_gemm(16, 8, 12, algo="ffip", dtype="float32", traced=True)
    assert r.get("repro_kernel_traces_total").labels(
        kernel="gemm", algo="ffip", dtype="float32").value == 1.0
    assert r.get("repro_kernel_dispatches_total").labels(
        kernel="gemm", algo="ffip", dtype="float32").value == 1.0


def test_kernel_hook_splits_dispatch_from_trace():
    """The real kernels.ops.matmul call site: an eager call is a dispatch;
    the same call under jax.jit is a compile-side trace."""
    from repro.kernels import ops
    prev = obs_profile.set_profiler(obs_profile.KernelProfiler(Registry()))
    try:
        prof = obs_profile.get_profiler()
        a = np.ones((16, 8), np.float32)
        b = np.ones((8, 16), np.float32)
        np.testing.assert_allclose(
            ops.matmul(jax.numpy.asarray(a), jax.numpy.asarray(b),
                       algo="ffip", interpret=True), a @ b, rtol=1e-6)
        lab = dict(kernel="gemm", algo="ffip", dtype="float32")
        assert prof.dispatches.labels(**lab).value == 1.0

        jax.jit(lambda x, y: ops.matmul(x, y, algo="ffip", interpret=True))(
            jax.numpy.asarray(a), jax.numpy.asarray(b)).block_until_ready()
        assert prof.traces.labels(**lab).value == 1.0
        assert prof.dispatches.labels(**lab).value == 1.0   # unchanged
    finally:
        obs_profile.set_profiler(prev)


def test_compile_snapshot_unifies_legacy_counters():
    snap = obs_profile.compile_snapshot()
    assert set(snap) == {"derived_cache", "schedule_cache", "measure"}
    assert "timed_candidates" in snap["measure"]


# -- watchdog single-source telemetry ----------------------------------------

def test_train_watchdog_shim_cannot_diverge():
    """The train shim is a pure alias: shared methods verbatim, no state of
    its own beyond the loop label default — double-bookkeeping is dead."""
    assert StepWatchdog.observe is Watchdog.observe
    assert StepWatchdog.check_hang is Watchdog.check_hang
    assert set(vars(StepWatchdog)) <= {"__init__", "__doc__", "__module__",
                                       "__qualname__", "__firstlineno__",
                                       "__static_attributes__"}


def test_watchdog_counters_labeled_by_loop():
    r = Registry()
    clock = FakeClock()
    cfg = WatchdogConfig(threshold=2.0, consecutive_to_act=2,
                         hang_timeout_s=5.0)
    train = StepWatchdog(cfg, clock=clock, registry=r)
    serve = Watchdog(cfg, clock=clock, registry=r, loop="serve")
    for dog in (train, serve):
        dog.observe(0, 1.0)
        dog.observe(1, 10.0)            # straggler
    straggler = r.get("watchdog_straggler_flags_total")
    assert straggler.labels(loop="train").value == 1.0
    assert straggler.labels(loop="serve").value == 1.0
    clock.advance(10.0)
    with pytest.raises(HangError):
        serve.check_hang()
    assert r.get("watchdog_deadman_trips_total").labels(
        loop="serve").value == 1.0
    assert len(train.events) <= train.events.maxlen


# -- serving integration -----------------------------------------------------

def test_batcher_clock_injection_and_fresh_stats_contract():
    """All batcher wall-clock reads go through the injected clock (a frozen
    FakeClock yields all-zero timings), and run_until_drained resets stats
    per drain while the obs registry + compile counts stay cumulative."""
    cfg, model, params = _setup()
    assert "perf_counter" not in inspect.getsource(
        __import__("repro.serve.batcher", fromlist=["batcher"]))
    clock = FakeClock()
    reg = Registry()
    srv = BatchServer(model, batch_slots=2, max_len=MAX_LEN, clock=clock,
                      registry=reg)
    prompts = _prompts(cfg)
    srv.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=MAX_NEW,
                       eos_id=-1))
    done = srv.run_until_drained(params)
    assert len(done) == 1
    first = dict(srv.stats)
    assert first["prefill_s"] == 0.0 and first["decode_s"] == 0.0
    assert done[0].t_done == done[0].t_submit == 0.0

    srv.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=MAX_NEW,
                       eos_id=-1))
    srv.run_until_drained(params)
    second = dict(srv.stats)
    # per-drain: the second dict describes ONLY the second request
    assert second["prefill_tokens"] == len(prompts[1])
    assert second["decode_tokens"] == MAX_NEW - 1
    # cumulative surfaces: registry counters span both drains
    tok = reg.get("serve_tokens_total")
    assert tok.labels(replica="solo", phase="prefill").value == \
        len(prompts[0]) + len(prompts[1])
    e2e = reg.get("serve_request_e2e_seconds").labels(replica="solo")
    assert e2e.count == 2 and e2e.quantile(0.99) == 0.0
    assert srv.compiles["prefill"] >= 1     # never reset by a drain
    assert reg.get("serve_compiles_total").labels(
        replica="solo", phase="prefill").value == srv.compiles["prefill"]


def test_batcher_events_ring_is_bounded():
    """The batcher's dispatch spans live in the tracer's ring, so a
    long-running server cannot leak them without bound."""
    cfg, model, params = _setup()
    srv = BatchServer(model, batch_slots=2, max_len=MAX_LEN, paged=True,
                      page_size=4, num_pages=24, prefill_chunk=4,
                      trace_capacity=16)
    for i, p in enumerate(_prompts(cfg)):
        srv.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW,
                           eos_id=-1))
    srv.run_until_drained(params)
    assert len(srv.tracer.spans) <= 16 and srv.tracer.dropped > 0
    ev = [s for s in srv.tracer.spans
          if s.name in ("prefill_chunk", "decode")]
    assert ev, "no dispatch span in the ring"
    for e in ev:
        if e.name == "prefill_chunk":
            assert isinstance(e.attrs["rid_int"], int)
            assert 0 <= e.attrs["start"] < e.attrs["end"]
        else:
            assert all(isinstance(r, int) for r in e.attrs["rids"])


def test_router_span_tree_for_retried_faulted_request():
    """ISSUE 9 acceptance: one request, retried across two replicas under a
    fault plan, reconstructs to a SINGLE span tree — root request span,
    lifecycle phase children in order, the retry event carrying the typed
    error, and both attempts' replica assignments visible."""
    cfg, model, params = _setup()
    servers = [BatchServer(model, batch_slots=2, max_len=MAX_LEN)
               for _ in range(2)]
    plan = FaultPlan([FaultSpec(kind="raise", replica=0, at_dispatch=0,
                                duration=2)], seed=3)
    rt = ReplicaRouter(servers, params, fault_plan=plan, clock=FakeClock(),
                       cfg=RouterConfig(step_timeout_s=5.0, quarantine_s=0.2,
                                        max_retries=4))
    for i, p in enumerate(_prompts(cfg)):
        rt.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW, eos_id=-1))
    recs = rt.drive(max_ticks=2000)
    assert all(r.state is Lifecycle.DONE for r in recs.values())
    assert rt.stats["retries"] >= 1

    # every rid has exactly one complete tree
    for rid in map(str, range(len(LENS))):
        spans = rt.tracer.completed(rid)
        roots = [s for s in spans if s.name == "request"]
        assert len(roots) == 1 and roots[0].t1 is not None, rid
        tree = rt.tracer.span_tree(rid)
        assert tree["attrs"]["outcome"] == "done"
        assert tree["children"], rid

    retried = [s.rid for s in rt.tracer.spans if s.name == "retry"]
    assert retried, "fault plan produced no retry event"
    tree = rt.tracer.span_tree(retried[0])
    flat = tree["children"]
    kinds = [c["name"] for c in flat]
    assert kinds[0] == "queued" and "retry" in kinds
    retry = next(c for c in flat if c["name"] == "retry")
    assert retry["attrs"]["error"] == "ReplicaFailedError"
    attempts = {c["attrs"].get("attempt") for c in flat}
    assert {0, 1} <= attempts
    # mirrored stats: every router stat equals its obs counter series
    for kind, v in rt.stats.items():
        got = rt.registry.get("router_events_total").labels(kind=kind).value
        assert got == v, (kind, got, v)


def test_router_e2e_histogram_feeds_quantiles():
    cfg, model, params = _setup()
    reg = Registry()
    servers = [BatchServer(model, batch_slots=2, max_len=MAX_LEN,
                           registry=reg)]
    rt = ReplicaRouter(servers, params, clock=FakeClock(), registry=reg,
                       cfg=RouterConfig(step_timeout_s=5.0))
    for i, p in enumerate(_prompts(cfg)):
        rt.submit(Request(rid=i, prompt=p, max_new_tokens=MAX_NEW, eos_id=-1))
    recs = rt.drive(max_ticks=2000)
    lat = sorted(r.t_done - r.t_submit for r in recs.values())
    h = reg.get("router_request_e2e_seconds")
    assert h.count == len(LENS)
    assert h.quantile(0.5) == pytest.approx(float(np.percentile(lat, 50)))
