"""The batcher's spans inside ``step``: the span tree, the batcher's queue
wait (``admit_wait``), the K/V rows a decode dispatch attends against the
rows its cache holds, their mirror on the profiler's clock, and the
inter-token latency charged from the time each token reached the host.

Every run here uses a clock that advances by one on each read, so span
bounds are distinct and comparisons between them are exact."""
import glob
import itertools

import jax
import numpy as np
import pytest

from repro import configs
from repro.models.model import build_model
from repro.obs import Registry, Tracer
from repro.serve import batcher as batcher_mod
from repro.serve.batcher import BatchServer, Request
from repro.serve.faults import FakeClock

MAX_LEN = 48
LENS = [5, 9, 3, 12, 7]
BUDGETS = [6, 3, 8, 4, 5]
# (paged, decode_chunk)
MODES = {"contiguous": (False, 1), "contiguous-chunk2": (False, 2),
         "paged": (True, 1)}
STEP_CHILDREN = {"params", "admit", "decode", "replay"}
PREFILL_CHILDREN = {"prefill.pack", "prefill.dispatch", "prefill.sync"}
DECODE_CHILDREN = {"decode.pack", "decode.dispatch", "decode.sync"}


class TickClock:
    """A clock that advances by one on every read."""

    def __init__(self):
        self._t = itertools.count()

    def __call__(self) -> float:
        return float(next(self._t))


@pytest.fixture(scope="module")
def smoke():
    cfg = configs.smoke_config(configs.get_config("minicpm-2b"))
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def _server(model, paged, chunk, **kw):
    extra = dict(paged=True, page_size=4, prefill_chunk=8) if paged else {}
    return BatchServer(model, batch_slots=2, max_len=MAX_LEN,
                       decode_chunk=chunk, **extra, **kw)


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [Request(rid=i, prompt=rng.integers(0, cfg.vocab, size=(n,)),
                    max_new_tokens=b, eos_id=-1)
            for i, (n, b) in enumerate(zip(LENS, BUDGETS))]


@pytest.fixture(scope="module", params=list(MODES))
def served(request, smoke):
    """One drained run per mode, with every ``jax.device_get`` the server
    makes counted: (mode, server, requests, device_get calls, bytes)."""
    cfg, model, params = smoke
    paged, chunk = MODES[request.param]
    srv = _server(model, paged, chunk, clock=TickClock())
    reqs = _requests(cfg)
    gets = []
    real_get = jax.device_get

    def counting_get(x):
        out = real_get(x)
        gets.append(np.asarray(out).nbytes)
        return out

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(batcher_mod.jax, "device_get", counting_get)
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained(params)
    return request.param, srv, reqs, len(gets), sum(gets)


def _by_sid(srv):
    return {s.sid: s for s in srv.tracer.spans}


def _kids(srv, span):
    return [s for s in srv.tracer.spans if s.parent == span.sid]


def test_step_span_tree(served):
    """Names and parents of the tree under each ``step``; ``prefill``'s
    bucket, rids and tokens; each ``decode``'s live rows equal to the rows
    its slots' positions attend, and its cache rows to what the cache
    holds."""
    mode, srv, reqs, _, _ = served
    paged, chunk = MODES[mode]
    assert srv.tracer.dropped == 0
    by_sid = _by_sid(srv)
    steps = [s for s in srv.tracer.spans if s.name == "step"]
    assert steps and all(s.parent is None and s.rid is None for s in steps)
    for st in steps:
        names = [k.name for k in _kids(srv, st)]
        assert names[:2] == ["params", "admit"]
        assert set(names) <= STEP_CHILDREN
        assert ("decode" in names) == ("replay" in names)
    prefill_name = "prefill_chunk" if paged else "prefill"
    for s in srv.tracer.spans:
        parent = by_sid.get(s.parent)
        if s.name in STEP_CHILDREN:
            assert parent.name == "step" and s.rid is None
        elif s.name in (prefill_name, "place"):
            assert parent.name == "admit"
        elif s.name in PREFILL_CHILDREN:
            assert parent.name == prefill_name and s.rid is None
        elif s.name in DECODE_CHILDREN:
            assert parent.name == "decode" and s.rid is None
        else:
            assert s.name in ("step", "request", "admit_wait"), s.name

    plen = {r.rid: len(r.prompt) for r in reqs}
    budget = {r.rid: r.max_new_tokens for r in reqs}
    prefills = [s for s in srv.tracer.spans if s.name == prefill_name]
    if paged:
        assert sum(s.attrs["end"] - s.attrs["start"] for s in prefills) \
            == sum(LENS)
    else:
        assert sorted(r for s in prefills for r in s.attrs["rids"]) \
            == sorted(plen)
        for s in prefills:
            assert s.attrs["tokens"] == sum(plen[r] for r in s.attrs["rids"])
            assert s.attrs["bucket"] >= max(plen[r] for r in s.attrs["rids"])
            assert [k.name for k in _kids(srv, s)] == [
                "prefill.pack", "prefill.dispatch", "prefill.sync"]

    rows = srv.num_pages * srv.page_size if paged else srv.b * MAX_LEN
    seen = dict.fromkeys(plen, 1)           # the first token, from prefill
    decodes = [s for s in srv.tracer.spans if s.name == "decode"]
    for d in decodes:
        assert [k.name for k in _kids(srv, d)] == [
            "decode.pack", "decode.dispatch", "decode.sync"]
        assert d.attrs["chunk"] == chunk
        assert d.attrs["cache_rows"] == chunk * rows
        want = 0
        for r in d.attrs["rids"]:
            pos = plen[r] + seen[r] - 1     # rows in the slot's cache
            n = min(chunk, budget[r] - seen[r])
            want += n * (pos + 1) + n * (n - 1) // 2
            seen[r] += n
        assert d.attrs["live_rows"] == want
        assert 0 < want <= d.attrs["cache_rows"]
    assert seen == budget
    replays = [s for s in srv.tracer.spans if s.name == "replay"]
    assert sum(s.attrs["emitted"] for s in replays) \
        == sum(BUDGETS) - len(BUDGETS)


def test_admit_wait_ends_at_admitting_prefill_start(served):
    """Each request's ``admit_wait`` runs from its submit, under its
    ``request`` span, to the start of the prefill dispatch that admits
    it."""
    mode, srv, reqs, _, _ = served
    paged, _ = MODES[mode]
    by_sid = _by_sid(srv)
    waits = {s.rid: s for s in srv.tracer.spans if s.name == "admit_wait"}
    assert sorted(waits) == sorted(str(r.rid) for r in reqs)
    for r in reqs:
        w = waits[str(r.rid)]
        assert by_sid[w.parent].name == "request"
        assert r.t_submit < w.t0 < w.t1
        if paged:
            first = min((s for s in srv.tracer.spans
                         if s.name == "prefill_chunk"
                         and s.attrs["rid_int"] == r.rid),
                        key=lambda s: s.t0)
        else:
            first = next(s for s in srv.tracer.spans
                         if s.name == "prefill" and r.rid in s.attrs["rids"])
        assert w.t1 == first.t0


def test_step_children_fit_inside_their_parent(served):
    """The children of ``step``, ``prefill`` and ``decode`` lie inside their
    parent one after another, so their durations sum to at most its own."""
    _, srv, _, _, _ = served
    for p in srv.tracer.spans:
        if p.name not in ("step", "prefill", "prefill_chunk", "decode"):
            continue
        kids = sorted(_kids(srv, p), key=lambda s: s.t0)
        assert kids
        assert p.t0 <= kids[0].t0 and kids[-1].t1 <= p.t1
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0
        assert sum(k.duration for k in kids) <= p.duration


def test_host_transfers_unchanged(served):
    """The spans add no device synchronisation and no transfer: one
    ``device_get`` per ``.sync`` span, moving exactly the bytes the
    ``host_bytes_*`` counters report."""
    mode, srv, reqs, n_gets, got_bytes = served
    paged, chunk = MODES[mode]
    st = srv.stats
    syncs = [s for s in srv.tracer.spans if s.name.endswith(".sync")]
    assert n_gets == len(syncs)
    assert got_bytes == st["host_bytes_prefill"] + st["host_bytes_decode"]
    assert st["host_bytes_decode"] == st["decode_dispatches"] * chunk * srv.b * 4
    if paged:
        assert st["host_bytes_prefill"] == 4 * len(reqs)
        assert st["host_bytes_page_tables"] == 4 * srv.max_pages * (
            st["prefill_chunks"] + srv.b * st["decode_dispatches"])
    else:
        assert st["host_bytes_prefill"] == st["prefill_dispatches"] * srv.b * 4
        assert st["host_bytes_page_tables"] == 0


def _host_events(pb):
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(pb)
    plane = next(p for p in prof.planes if p.name == "/host:CPU")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in plane.lines for e in line.events
            if e.name == "caller" or e.name.startswith("serve.")]


def test_spans_mirrored_on_the_profiler_clock(smoke, tmp_path):
    """Under a profiler session the host plane holds a ``serve.<name>``
    event for every span opened in a ``with`` block, nested inside the
    caller's annotation as the spans nest, and the ring holds the same
    spans."""
    cfg, model, params = smoke
    srv = _server(model, False, 1)
    for r in _requests(cfg)[:3]:
        srv.submit(r)
    srv.step(params)                        # compile outside the trace
    n0 = len(srv.tracer.spans)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("caller"):
                srv.step(params)
    ring = [s for s in list(srv.tracer.spans)[n0:]
            if s.name not in ("request", "admit_wait")]
    events = _host_events(glob.glob(f"{tmp_path}/**/*.xplane.pb",
                                    recursive=True)[0])
    callers = [e for e in events if e[0] == "caller"]
    mirrored = [e for e in events if e[0] != "caller"]
    assert len(callers) == 3
    assert sorted(e[0] for e in mirrored) == sorted(
        "serve." + s.name for s in ring)
    for name, a, b in mirrored:
        assert any(ca <= a and b <= cb for _, ca, cb in callers), name
    # nesting: each mirrored child lies inside its parent's mirror
    by_sid = {s.sid: s for s in ring}
    order = sorted(mirrored, key=lambda e: e[1])
    spans = sorted(ring, key=lambda s: s.t0)
    assert [e[0] for e in order] == ["serve." + s.name for s in spans]
    ev_of = {s.sid: e for s, e in zip(spans, order)}
    for s in ring:
        if s.parent in by_sid:
            _, a, b = ev_of[s.sid]
            _, pa, pb = ev_of[s.parent]
            assert pa <= a and b <= pb, s.name


def test_tracer_without_factory_records_same_spans(smoke, monkeypatch):
    """A server whose tracer has no annotation factory records the same
    ring spans as one whose tracer mirrors them, and opens no
    annotation."""
    cfg, model, params = smoke
    opened = []

    class Recorder:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(batcher_mod, "_annotate", Recorder)
    rings = []
    for mirror in (True, False):
        clock = TickClock()
        srv = _server(model, False, 1, clock=clock,
                      tracer=None if mirror else Tracer(clock=clock))
        for r in _requests(cfg):
            srv.submit(r)
        srv.run_until_drained(params)
        rings.append([(s.name, s.sid, s.parent, s.rid, s.t0, s.t1, s.attrs)
                      for s in srv.tracer.spans])
        if mirror:
            mirrored = len([s for s in srv.tracer.spans
                            if s.name not in ("request", "admit_wait")])
            assert len(opened) == mirrored > 0
            opened.clear()
    assert rings[0] == rings[1]
    assert opened == []


@pytest.mark.parametrize("chunk", [1, 2])
def test_itl_charges_time_since_previous_token(smoke, chunk):
    """Each decoded token is charged the time since its request's previous
    token reached the host, split evenly over the tokens of one fused
    chunk, so an admission prefill that stalls decode shows up in the
    inter-token latency of the requests already decoding."""
    cfg, model, params = smoke
    clock = FakeClock()
    srv = _server(model, False, chunk, clock=clock, registry=Registry())
    for name, cost in (("_prefill_bucket", 5.0), ("_decode", 1.0)):
        program = getattr(srv, name)

        def timed(*args, _program=program, _cost=cost):
            clock.advance(_cost)
            return _program(*args)

        setattr(srv, name, timed)
    a, b = _requests(cfg)[:2]
    a.max_new_tokens = 7
    srv.submit(a)
    srv.step(params)                        # prefill a, decode 1 chunk
    srv.submit(b)                           # its prefill stalls a's decode
    srv.run_until_drained(params)
    assert a.t_first == 5.0
    want = [1.0 / chunk] * chunk + [6.0 / chunk] * chunk
    want += [1.0 / chunk] * (a.max_new_tokens - 1 - len(want))
    assert a.itl_s == pytest.approx(want)
    itl = srv.registry.get("serve_itl_window_seconds")
    assert itl.count() == len(a.itl_s) + len(b.itl_s)
