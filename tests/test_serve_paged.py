"""Block-paged KV cache serving (ISSUE 6).

Covers:
  * PageAllocator refcount/free-list invariants under random churn;
  * chained prefix keys (equal iff the whole prefix matches) and the
    LRU prefix index's reference discipline;
  * bit-identity of paged serving vs the retained contiguous oracle —
    float AND int8-FFIP, GQA (minicpm) AND absorbed-MLA (deepseek),
    decode_chunk 1 and 4, gather and flash paged attention — on a
    mixed-length shared-prefix workload;
  * chunked prefill == single-dispatch prefill, and its interleaving with
    decode (a long prompt must not stall active slots);
  * prefix sharing: shared pages prefilled once (hit counters), COW when a
    shared tail page is decoded into, identical greedy continuations;
  * paged capacity boundary (same cache_rows contract as contiguous),
    pool exhaustion (clean error, no hang) and leak-free teardown.

attention_impl is forced to "naive" so the contiguous oracle and the paged
gather path run literally the same einsums — bit-identity, not allclose.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro import configs
from repro.models.model import build_model
from repro.serve.batcher import BatchServer, Request
from repro.serve.lifecycle import AdmissionImpossibleError
from repro.serve.paged import (PageAllocator, PrefixIndex, page_keys,
                               partial_key)

MAX_LEN = 48
PS = 8

_MODELS = {}
_REF = {}


def _setup(arch):
    if arch not in _MODELS:
        cfg = configs.smoke_config(configs.get_config(arch))
        cfg = dataclasses.replace(cfg, attention_impl="naive")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _MODELS[arch] = (cfg, model, params)
    return _MODELS[arch]


def _workload(cfg, seed=0):
    """Mixed lengths + shared prefixes + an exact resubmission."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, cfg.vocab, size=(20,))
    reqs = []
    for i in range(3):          # 3 prompts sharing a 16-token (2-page) prefix
        tail = rng.integers(0, cfg.vocab, size=(3 + i,))
        reqs.append((np.concatenate([base[:16], tail]), 6))
    reqs.append((reqs[0][0].copy(), 4))          # identical full prompt
    for n, m in [(5, 8), (30, 10), (1, 3), (44, 5)]:
        reqs.append((rng.integers(0, cfg.vocab, size=(n,)), m))
    return reqs


def _run(srv, reqs, params):
    for i, (p, m) in enumerate(reqs):
        srv.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    done = srv.run_until_drained(params)
    return {r.rid: list(r.out_tokens) for r in done}


def _contiguous_ref(arch, quantized):
    key = (arch, quantized)
    if key not in _REF:
        cfg, model, params = _setup(arch)
        srv = BatchServer(model, batch_slots=3, max_len=MAX_LEN,
                          quantized=quantized)
        _REF[key] = _run(srv, _workload(cfg), params)
    return _REF[key]


# -- host-side bookkeeping ----------------------------------------------------

def test_page_allocator_invariants_under_churn():
    rng = np.random.default_rng(0)
    a = PageAllocator(32)
    refs = {}                                    # page -> expected refcount
    for _ in range(2000):
        op = int(rng.integers(0, 3))
        if op == 0 and a.free_count:
            p = a.alloc()
            assert p not in refs, "alloc returned a still-referenced page"
            refs[p] = 1
        elif op == 1 and refs:
            p = int(rng.choice(list(refs)))
            a.incref(p)
            refs[p] += 1
        elif op == 2 and refs:
            p = int(rng.choice(list(refs)))
            freed = a.decref(p)
            refs[p] -= 1
            assert freed == (refs[p] == 0)
            if refs[p] == 0:
                del refs[p]
        assert a.free_count + a.in_use == a.num_pages
        assert a.in_use == len(refs)
        for p, r in refs.items():
            assert a.refcount(p) == r
    while a.free_count:
        refs[a.alloc()] = 1
    assert a.peak_in_use == a.num_pages
    with pytest.raises(RuntimeError):
        a.alloc()


def test_prefix_keys_chained():
    rng = np.random.default_rng(1)
    a = rng.integers(0, 1000, size=(25,))
    b = a.copy()
    b[18] += 1                                   # diverge inside page 2
    ka, kb = page_keys(a, 8), page_keys(b, 8)
    assert len(ka) == 3
    assert ka[:2] == kb[:2], "identical prefix pages must share keys"
    assert ka[2] != kb[2], "divergent page must differ"
    assert partial_key(a, 8) != partial_key(b, 8), \
        "partial key must commit to the whole upstream chain"
    assert partial_key(a[:24], 8) is None, "aligned prompt has no tail"
    assert partial_key(a[:20], 8) != partial_key(a[:21], 8), \
        "tail LENGTH is part of the key"
    d = a.copy()
    d[24] += 1
    assert partial_key(a, 8) != partial_key(d, 8), \
        "tail CONTENT is part of the key"


def test_prefix_index_holds_refs_and_evicts_lru():
    a = PageAllocator(8)
    idx = PrefixIndex(a)
    p0, p1 = a.alloc(), a.alloc()
    idx.register(b"k0", p0)
    idx.register(b"k1", p1)
    assert a.refcount(p0) == 2, "index holds its own reference"
    idx.register(b"k0", p0)                      # idempotent
    assert a.refcount(p0) == 2
    a.decref(p0)                                 # owner finishes
    assert idx.get(b"k0") == p0, "page outlives its owner via the index"
    assert a.refcount(p0) == 1
    # get(k0) promoted it, so the LRU victim is k1 — whose owner still
    # holds a reference: eviction drops the index entry, frees nothing.
    assert idx.evict_lru(1) == 0
    assert idx.get(b"k1") is None
    assert a.refcount(p1) == 1
    assert idx.evict_lru(1) == 1                 # k0 unreferenced -> freed
    assert len(idx) == 0
    assert a.in_use == 1                         # only p1's owner ref left


# -- bit-identity vs the contiguous oracle ------------------------------------

@pytest.mark.parametrize("arch", ["minicpm-2b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("quantized,decode_chunk,paged_attention", [
    (False, 1, "gather"),
    (False, 4, "gather"),
    (True, 4, "gather"),
    (False, 4, "flash"),
])
def test_paged_bit_identical_to_contiguous(arch, quantized, decode_chunk,
                                           paged_attention):
    cfg, model, params = _setup(arch)
    want = _contiguous_ref(arch, quantized)
    srv = BatchServer(model, batch_slots=3, max_len=MAX_LEN,
                      quantized=quantized, decode_chunk=decode_chunk,
                      paged=True, page_size=PS, prefill_chunk=16,
                      paged_attention=paged_attention)
    got = _run(srv, _workload(cfg), params)
    assert got == want, {k: (got.get(k), want[k]) for k in want
                         if got.get(k) != want[k]}
    # prefix sharing keeps the footprint under the contiguous equivalent
    assert srv.stats["pages_peak"] < srv.b * srv.max_pages
    assert srv.stats["prefix_hit_tokens"] > 0
    assert srv._reserved == 0, "reservation ledger must drain"
    assert srv.alloc.free_count + srv.alloc.in_use == srv.alloc.num_pages


def test_chunked_prefill_equivalent_to_single_dispatch():
    cfg, model, params = _setup("minicpm-2b")
    want = _contiguous_ref("minicpm-2b", False)
    srv = BatchServer(model, batch_slots=3, max_len=MAX_LEN, paged=True,
                      page_size=PS, prefill_chunk=PS)   # smallest legal chunk
    got = _run(srv, _workload(cfg), params)
    assert got == want
    # the 30- and 44-token prompts really did split into several chunks
    assert srv.stats["prefill_chunks"] > len(want)


# -- prefix sharing & chunk interleaving --------------------------------------

def _run1(srv, params, rid, prompt, max_new):
    srv.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    done = srv.run_until_drained(params)
    assert [r.rid for r in done] == [rid]
    return list(done[0].out_tokens)


def test_prefix_sharing_prefills_once_and_cows_shared_tail():
    cfg, model, params = _setup("minicpm-2b")
    srv = BatchServer(model, batch_slots=1, max_len=MAX_LEN, paged=True,
                      page_size=PS, prefill_chunk=PS)
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab, size=(20,))    # 2 full pages + 4 tail
    a = _run1(srv, params, 0, base, 4)
    assert srv.stats["prefix_hit_tokens"] == 0
    assert srv.stats["prefill_tokens"] == 20
    # B shares A's two full pages, diverges after: only the new suffix runs
    b_prompt = np.concatenate([base[:16],
                               rng.integers(0, cfg.vocab, size=(6,))])
    _run1(srv, params, 1, b_prompt, 4)
    assert srv.stats["prefix_hit_tokens"] == 16
    assert srv.stats["prefill_tokens"] == 6
    # C resubmits A's prompt verbatim: whole-prompt hit including the
    # partial tail page. Only the LAST token is recomputed (its hidden
    # state feeds the first sample) and NOTHING is rewritten; the first
    # decode write then copy-on-writes the shared tail page.
    c = _run1(srv, params, 2, base, 4)
    assert srv.stats["prefix_hit_tokens"] == 20
    assert srv.stats["prefill_tokens"] == 1
    assert srv.stats["cow_copies"] == 1
    assert c == a, "greedy continuation of an identical prompt must match"
    assert srv._reserved == 0


def test_long_prefill_interleaves_with_decode():
    cfg, model, params = _setup("minicpm-2b")
    srv = BatchServer(model, batch_slots=2, max_len=MAX_LEN, paged=True,
                      page_size=PS, prefill_chunk=PS, prefix_sharing=False)
    rng = np.random.default_rng(9)
    srv.submit(Request(rid=0, prompt=rng.integers(0, cfg.vocab, size=(4,)),
                       max_new_tokens=20))
    srv.step(params)
    srv.step(params)                              # rid 0 is mid-decode
    srv.submit(Request(rid=1, prompt=rng.integers(0, cfg.vocab, size=(40,)),
                       max_new_tokens=4))
    srv.run_until_drained(params)
    ev = [s for s in srv.tracer.spans
          if s.name in ("prefill_chunk", "decode")]
    chunks = [i for i, e in enumerate(ev)
              if e.name == "prefill_chunk" and e.attrs["rid_int"] == 1]
    assert len(chunks) == 5, "40-token prompt must split into 5 8-token chunks"
    for lo, hi in zip(chunks, chunks[1:]):
        assert any(e.name == "decode" and 0 in e.attrs["rids"]
                   for e in ev[lo:hi]), \
            "active slot must keep decoding between the long prompt's chunks"


# -- capacity, exhaustion, teardown -------------------------------------------

def test_paged_capacity_boundary_and_pool_exhaustion():
    cfg, model, params = _setup("minicpm-2b")
    rng = np.random.default_rng(11)
    p12 = rng.integers(0, cfg.vocab, size=(12,))
    # prompt + max_new - 1 == max_len fits exactly (same cache_rows contract
    # as the contiguous path) and uses exactly ceil(max_len / ps) pages
    srv = BatchServer(model, batch_slots=1, max_len=16, paged=True,
                      page_size=4)
    out = _run1(srv, params, 0, p12, 5)
    assert len(out) == 5
    assert srv.stats["pages_peak"] == 4
    with pytest.raises(ValueError):
        srv.submit(Request(rid=9, prompt=p12, max_new_tokens=6))
    # a request whose worst case exceeds the whole POOL fails loudly at
    # submit time (typed, still a ValueError) instead of entering the
    # queue and hanging it forever
    srv2 = BatchServer(model, batch_slots=2, max_len=16, paged=True,
                       page_size=4, num_pages=2)
    with pytest.raises(AdmissionImpossibleError):
        srv2.submit(Request(rid=0, prompt=p12, max_new_tokens=2))
    assert srv2._reserved == 0
    # a pool smaller than slots x max_pages just queues: admission waits for
    # running requests to release pages, everything still completes
    srv3 = BatchServer(model, batch_slots=2, max_len=16, paged=True,
                       page_size=4, num_pages=4, prefix_sharing=False)
    prompts = [rng.integers(0, cfg.vocab, size=(8,)) for _ in range(3)]
    for i, p in enumerate(prompts):                 # each needs 3 of 4 pages
        srv3.submit(Request(rid=i, prompt=p, max_new_tokens=4))
    done = srv3.run_until_drained(params)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.out_tokens) == 4 for r in done)
    assert srv3.alloc.in_use == 0, "no sharing -> every page returns"
    assert srv3._reserved == 0


def test_paged_rejects_unsupported_configs():
    cfg, model, params = _setup("minicpm-2b")
    with pytest.raises(ValueError):                 # non-power-of-two page
        BatchServer(model, batch_slots=1, max_len=48, paged=True, page_size=6)
    with pytest.raises(ValueError):                 # max_len not page-aligned
        BatchServer(model, batch_slots=1, max_len=50, paged=True, page_size=8)
    with pytest.raises(ValueError):                 # chunk not page-aligned
        BatchServer(model, batch_slots=1, max_len=48, paged=True, page_size=8,
                    prefill_chunk=12)
    ssm = build_model(configs.smoke_config(configs.get_config(
        "falcon-mamba-7b")))
    with pytest.raises(ValueError):                 # SSM state is not rows
        BatchServer(ssm, batch_slots=1, max_len=48, paged=True, page_size=8)


# -- ISSUE 8 satellites: faulted/aborted requests must drain the ledger


def test_abort_mid_prefill_releases_reservation_and_keeps_index_clean():
    """Abort a request halfway through chunked prefill: its page
    reservation returns to the ledger (drains to 0), the allocator
    invariant holds, and only FULLY COMPUTED prompt pages were published to
    the prefix index — a resubmission completes with oracle tokens."""
    cfg, model, params = _setup("minicpm-2b")
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab, size=(30,))

    ref = BatchServer(model, batch_slots=1, max_len=MAX_LEN)
    ref.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    want = list(ref.run_until_drained(params)[0].out_tokens)

    srv = BatchServer(model, batch_slots=2, max_len=MAX_LEN, paged=True,
                      page_size=PS, num_pages=12, prefill_chunk=PS)
    srv.submit(Request(rid=0, prompt=prompt, max_new_tokens=5))
    srv.step(params)                  # admit + first 8-token prefill chunk
    assert srv.request_phase(0) == "prefilling"
    assert srv._reserved > 0
    assert srv.abort(0)
    assert srv._reserved == 0
    assert srv.alloc.free_count + srv.alloc.in_use == srv.num_pages
    # only the one completed page is published; rows 8.. were never
    # computed, so their keys must NOT serve future prefix hits
    assert len(srv.prefix) <= 1
    srv.submit(Request(rid=1, prompt=prompt, max_new_tokens=5))
    done = srv.run_until_drained(params)
    assert len(done) == 1 and list(done[0].out_tokens) == want
    assert srv._reserved == 0


def test_pool_churn_with_mid_prefill_aborts_never_leaks():
    """Heavy churn through a small pool with prefix sharing and periodic
    mid-prefill aborts: LRU eviction keeps admission alive, every surviving
    request matches its fresh-server oracle, and the allocator/ledger end
    exactly clean."""
    cfg, model, params = _setup("minicpm-2b")
    rng = np.random.default_rng(12)
    base = rng.integers(0, cfg.vocab, size=(16,))
    prompts = [np.concatenate([base, rng.integers(0, cfg.vocab, size=(8,))])
               for _ in range(6)]

    def oracle(p):
        ref = BatchServer(model, batch_slots=1, max_len=MAX_LEN)
        ref.submit(Request(rid=0, prompt=p, max_new_tokens=4))
        return list(ref.run_until_drained(params)[0].out_tokens)

    srv = BatchServer(model, batch_slots=2, max_len=MAX_LEN, paged=True,
                      page_size=PS, num_pages=10, prefill_chunk=PS)
    survivors = {}
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=p, max_new_tokens=4))
        if i % 2 == 0:
            srv.step(params)          # partway into prefill...
            srv.abort(i)              # ...then gone
        else:
            done = srv.run_until_drained(params)
            for r in done:
                survivors[r.rid] = list(r.out_tokens)
        assert srv._reserved == 0 or srv.request_phase(i) is not None
        assert srv.alloc.free_count + srv.alloc.in_use == srv.num_pages
    assert sorted(survivors) == [1, 3, 5]
    for rid, toks in survivors.items():
        assert toks == oracle(prompts[rid]), rid
    # end state: nothing reserved, every page accounted for, and the index
    # holds at most the pool (shared-prefix pages were evicted under churn)
    assert srv._reserved == 0
    assert srv.alloc.free_count + srv.alloc.in_use == srv.num_pages
    assert len(srv.prefix) <= srv.num_pages
