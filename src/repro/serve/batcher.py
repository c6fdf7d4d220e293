"""Serving runtime: slot-based continuous batching over prefill/decode steps.

A fixed pool of B slots; requests occupy a slot, prefill writes their prompt
into the slot's cache region, then all active slots decode in lockstep at
their OWN positions: a ``(B,)`` position vector flows through the decode
program, so each slot writes its KV rows, applies rope, and masks attention
at its true offset (mixed-length prompts decode correctly side by side).
Finished slots (EOS or max_tokens) are immediately refilled from the queue —
the standard continuous-batching scheme (vLLM-style, simplified to
fixed-shape slots so XLA shapes stay static).

Hot-path discipline (the paper's Eq. 15 / §4.4 move — hoist everything off
the critical path — applied to serving):

* **On-device sampling**: the decode program ends in a fused argmax
  (``Model.sample_steps``); only ``(chunk, B)`` int32 token ids cross to the
  host per dispatch, never the ``(B, V)`` float logits.
* **Fused multi-step decode**: ``decode_chunk`` steps run as one
  ``lax.scan`` that feeds sampled tokens back on device, with per-slot
  position/remaining/EOS masking — a finished slot freezes and re-writes its
  own cache row with identical values, so the cache (and therefore every
  emitted token) stays bit-identical to one-step-at-a-time decode while host
  round-trips per token drop by 1/chunk.
* **Bucketed batched prefill**: prompts are padded to power-of-2 length
  buckets and same-bucket requests prefill together in ONE dispatch, written
  straight into the shared slot cache via one masked
  ``dynamic_update_slice`` per leaf and layer (``Model.prefill_sample``) —
  no batch-1 scratch cache, no per-leaf scatter, and the prefill jit cache
  is bounded to O(log max_len) entries instead of one per distinct prompt
  length.
* **In-place cache writes**: the cache is donated to every program and the
  layer scan carries the stacked leaves, so each layer writes only its new
  rows into them where they lie (in decode, the aligned block of rows that
  holds each slot's new row) and reads its K/V from there: no dispatch
  copies a layer or a whole leaf of the cache.

With ``quantized=True`` the dense/attention projections of the serving
forward route through the paper's int8 FFIP path: weights are quantized
OFFLINE (per-output-channel, asymmetric) with beta folded into the integer
bias (Eq. 15) and colsums precomputed; at decode time the Eq. 20 zero-point
adjuster removes the zero-point cross terms. Activations quantize per token
row, so batched, bucketed, and chunk-fused decoding all stay bit-identical
to sequential decoding.

**Paged mode** (``paged=True``): the per-slot ``slots x max_len`` contiguous
cache is replaced by a shared page POOL per cache leaf (``num_pages`` pages
of ``page_size`` tokens) addressed through a per-slot ``(B, max_pages)``
int32 page table. Pages are allocated on demand as a sequence grows, full
prompt pages are keyed by a rolling hash and SHARED across requests with
identical prefixes (refcounted; copy-on-write when a shared page would be
partially overwritten), and long prompts prefill in page-aligned CHUNKS —
one chunk dispatch per slot per step, interleaved with decode dispatches,
so a long prefill no longer stalls already-active slots. The contiguous
path is retained untouched as the bit-exactness oracle: with
``paged_attention="gather"`` the paged decode gathers pool rows into the
contiguous layout and runs the identical attention math, so emitted tokens
are bit-identical to ``paged=False`` (float and int8-FFIP alike).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as obs
from repro.core.gemm import GemmConfig, use_gemm
from repro.dist import context as dist_context
from repro.dist import sharding as dist_sharding
from repro.models.model import Model
from repro.models.transformer import paged_cache_supported
from repro.obs.trace import Tracer
from repro.serve.lifecycle import AdmissionImpossibleError, ServeStallError
from repro.serve.paged import (PageAllocator, PrefixIndex, page_keys,
                               partial_key)

_MIN_BUCKET = 4


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1              # -1: never
    out_tokens: Optional[List[int]] = None
    t_submit: float = 0.0         # set by submit()
    t_first: float = 0.0          # set when the first token lands (TTFT)
    t_done: float = 0.0           # set when the request completes (e2e)
    # per-token inter-token latency (seconds): one entry per decoded token
    # after the first, mirroring what lands in serve_itl_window_seconds —
    # the raw list serve_bench cross-checks the windowed percentiles against.
    # Each token is charged the time since the request's previous token
    # reached the host (tokens of one fused chunk share it evenly), so a
    # prefill that stalls decode shows up here.
    itl_s: Optional[List[float]] = None


@dataclasses.dataclass
class _PagedSeq:
    """Paged-mode bookkeeping for one in-flight request."""
    n: int                        # prompt length
    pages: List[int]              # pool page ids for logical pages 0..k-1
    keys: List[bytes]             # chain keys of the FULL prompt pages
    pkey: Optional[bytes]         # key of the terminal partial page (if any)
    filled: int                   # leading prompt rows already in the pool
    compute_next: int             # next prompt token index to run
    shared_tail: bool             # pages[-1] attached shared -> COW on write
    reserve: int                  # pages reserved (admission) not yet alloc'd
    registered: int = 0           # full prompt pages published to the index


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0                  # tokens currently in this slot's cache rows
    remaining: int = 0
    seq: Optional[_PagedSeq] = None   # paged mode only
    t_tok: float = 0.0            # when the request's latest token reached
                                  # the host (inter-token latency)


def _annotate(name: str):
    """The profiler annotation that mirrors a batcher span, so a device trace
    shows what the host was doing on the device's clock."""
    return jax.profiler.TraceAnnotation("serve." + name)


def _cache_batch_axes(model: Model, batch: int, max_len: int):
    """Locate the batch axis of every cache leaf STRUCTURALLY: the axis whose
    size changes when init_cache's batch argument changes. Unlike sniffing for
    a dim that equals the slot count, this can never confuse a stacked layer
    (or head/state) dim that happens to equal the number of slots."""
    c_a = jax.eval_shape(lambda: model.init_cache(batch, max_len))
    c_b = jax.eval_shape(lambda: model.init_cache(batch + 1, max_len))

    def axis(a, b):
        return next(i for i, (sa, sb) in enumerate(zip(a.shape, b.shape))
                    if sa != sb)

    return jax.tree.map(axis, c_a, c_b)


def _cache_supports_buckets(model: Model, batch: int, max_len: int) -> bool:
    """Bucketed prefill needs every cache leaf to have a sequence axis (one
    that scales with max_len) so masked prefill-at-offset-0 commits exactly
    the prompt rows. SSM/hybrid state and encoder cross-KV leaves don't
    (their state is a running summary, not addressable rows), so those
    families fall back to the per-slot scatter prefill."""
    c_a = jax.eval_shape(lambda: model.init_cache(batch, max_len))
    c_b = jax.eval_shape(lambda: model.init_cache(batch, max_len + 1))
    return all(
        any(sa != sb for sa, sb in zip(a.shape, b.shape))
        for a, b in zip(jax.tree.leaves(c_a), jax.tree.leaves(c_b)))


class BatchServer:
    """Single-host reference implementation (the multi-pod serve path lowers
    the same decode step through launch/dryrun.py).

    ``decode_chunk`` is the fused-decode knob: steps per decode dispatch
    (1 = classic one-round-trip-per-token lockstep). ``prefill_buckets``
    enables bucketed batched prefill where the cache layout supports it.
    """

    def __init__(self, model: Model, *, batch_slots: int, max_len: int,
                 greedy: bool = True, quantized: bool = False,
                 gemm_algo: str = "ffip", gemm_impl: Optional[str] = None,
                 gemm_block=None, decode_chunk: int = 1,
                 prefill_buckets: bool = True, paged: bool = False,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 paged_attention: str = "gather",
                 prefix_sharing: bool = True, mesh=None,
                 moe_partition: str = "expert", prepared=None,
                 clock=None, registry=None, tracer=None,
                 trace_capacity: int = 4096, obs_window_s: float = 30.0):
        if not greedy:
            raise NotImplementedError("only greedy decoding is implemented")
        if decode_chunk < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {decode_chunk}")
        if mesh is not None and paged:
            raise NotImplementedError(
                "paged=True with mesh= is not supported yet (the page pool "
                "is host-managed per device); use the contiguous cache for "
                "tensor-parallel serving")
        if prepared is not None:
            if prepared.kind != "lm":
                raise ValueError(
                    f"BatchServer needs an 'lm' artifact, got "
                    f"{prepared.kind!r}")
            if quantized and not prepared.quantized:
                raise ValueError(
                    "quantized=True but the prepared artifact carries no "
                    "int8 weights — re-run `python -m repro.launch.prepare "
                    "--quantized`")
        self.model = model
        self.b = batch_slots
        self.max_len = max_len
        self.decode_chunk = decode_chunk
        self.paged = paged
        self.quantized = quantized   # the router's tier tag (shed policy)
        self.tier = "int8" if quantized else "float"
        self.obs_window_s = obs_window_s  # sliding-window span for TTFT/ITL
        # dist x serve: `mesh` turns on tensor-parallel decode. Params and
        # cache are placed through the repro.dist rule engine (column/row-
        # parallel projections + KV-head sharding on the "model" axis,
        # expert- or ffn-parallel MoE banks per `moe_partition`) and every
        # dispatch traces under the ambient mesh so flash attention's
        # shard_map engages. int8 output tokens match single-device on the
        # CPU and on TPU (chip_smoke.py --chips 4); float tokens match on the
        # CPU only: on TPU the row-parallel projections sum bf16 partials
        # across chips in another order than one chip's accumulator.
        self.mesh = mesh
        self.moe_partition = moe_partition
        self.prepared = prepared
        # -- observability (repro.obs) --------------------------------------
        # Every wall-clock read in this class goes through `_clock` — inject
        # a serve.faults.FakeClock (like ReplicaRouter takes) and all stats /
        # histograms / span timestamps become deterministic on fake time.
        self._clock = clock if clock is not None else obs.default_clock
        self.registry = (registry if registry is not None
                         else obs.get_registry())
        self.tracer = tracer if tracer is not None else Tracer(
            clock=self._clock, capacity=trace_capacity, annotate=_annotate)
        # The router relabels per replica via set_obs_labels() and sets
        # trace_requests=False (it owns the per-rid root "request" span —
        # two roots per rid would split the tree).
        self.trace_requests = True
        self._req_spans: Dict[int, Any] = {}
        # rid -> open "admit_wait" span: submit until the prefill dispatch
        # that admits the request starts (the batcher's own queue)
        self._wait_spans: Dict[int, Any] = {}
        self.set_obs_labels({"replica": "solo"})
        self.slots = [_Slot() for _ in range(batch_slots)]
        self._queue: "collections.deque[Request]" = collections.deque()
        self._completed: List[Request] = []
        # idempotency: rid -> (payload key, tokens) for finished requests
        # (bounded LRU); duplicate submits of an INFLIGHT rid wait here and
        # are completed from the original's tokens without a second decode.
        self._results: "collections.OrderedDict[int, Tuple[tuple, List[int]]]" \
            = collections.OrderedDict()
        self._result_cache_size = 1024
        self._dup_waiters: Dict[int, List[Request]] = {}
        self._cached_hits: List[Request] = []
        if paged:
            if page_size < 1 or (page_size & (page_size - 1)):
                raise ValueError(f"page_size must be a power of two, "
                                 f"got {page_size}")
            if max_len % page_size:
                raise ValueError(f"max_len ({max_len}) must be a multiple of "
                                 f"page_size ({page_size})")
            if not paged_cache_supported(model.cfg):
                raise ValueError("paged=True requires a pure-attention "
                                 f"decoder (family={model.cfg.family!r})")
            if paged_attention not in ("gather", "flash"):
                raise ValueError(f"paged_attention must be 'gather' or "
                                 f"'flash', got {paged_attention!r}")
            self.page_size = page_size
            self.max_pages = max_len // page_size
            self.num_pages = (num_pages if num_pages is not None
                              else batch_slots * self.max_pages)
            self.prefill_chunk = prefill_chunk or max_len
            if (self.prefill_chunk % page_size
                    or not 0 < self.prefill_chunk <= max_len):
                raise ValueError(
                    f"prefill_chunk ({self.prefill_chunk}) must be a "
                    f"page-aligned length in (0, max_len]")
            self.paged_attention = paged_attention
            self.prefix_sharing = prefix_sharing
            self.alloc = PageAllocator(self.num_pages)
            self.prefix = PrefixIndex(self.alloc)
            self._reserved = 0          # pages promised to admitted requests
            self.cache = model.init_paged_cache(self.num_pages, page_size)
            self._bucketed = False
            self._batch_axes = None
            self._decode_paged = jax.jit(self._decode_paged_impl,
                                         donate_argnums=(2,))
            self._prefill_chunk_fn = jax.jit(self._prefill_chunk_impl,
                                             donate_argnums=(2,))
            self._copy_page = jax.jit(
                lambda cache, src, dst: jax.tree.map(
                    lambda leaf: leaf.at[:, dst].set(leaf[:, src]), cache),
                donate_argnums=(0,))
        else:
            self.cache = model.init_cache(batch_slots, max_len)
            self._bucketed = (prefill_buckets
                              and _cache_supports_buckets(model, batch_slots,
                                                          max_len))
            self._batch_axes = (None if self._bucketed else
                                _cache_batch_axes(model, batch_slots, max_len))
            if mesh is not None:
                specs = dist_sharding.cache_specs(self.cache, mesh,
                                                  batch=batch_slots)
                self.cache = jax.device_put(
                    self.cache, dist_sharding.to_named(specs, mesh))
        # GEMM provider scope for the whole serving forward. ``gemm_impl``
        # ("pallas") routes the projections through the Pallas kernels and
        # ``gemm_block`` ("auto" / explicit (bm,bn,bk)) picks their tiling
        # from the repro.tune schedule cache — so the PR 3 hot path runs
        # under tuned blocks instead of one hardcoded constant. block="auto"
        # also drives tuned flash-attention (bq, bk) during prefill, which is
        # why a config is built even when impl stays "xla".
        if quantized or gemm_impl is not None or gemm_block is not None:
            impl = gemm_impl or "xla"
            if (gemm_block is not None and gemm_block != "auto"
                    and impl != "pallas"):
                # explicit (bm,bn,bk) only reaches a kernel through the
                # pallas provider; on xla it would be a silent no-op — the
                # exact failure mode the tuner exists to remove.
                raise ValueError(
                    "explicit gemm_block requires gemm_impl='pallas' "
                    "(block='auto' alone is fine: it also drives flash "
                    "attention's tuned blocks)")
            algo = gemm_algo if (quantized or impl == "pallas") else "baseline"
            self._gemm_cfg = GemmConfig(algo=algo, impl=impl,
                                        quantized=quantized, block=gemm_block)
        else:
            self._gemm_cfg = None
        self._qparams = None
        self._qparams_src = None
        self._placed = None
        self._placed_src = None
        self._decode = jax.jit(self._decode_impl, donate_argnums=(2,))
        # bucketed: one jit entry per power-of-2 prompt bucket.
        # fallback: batch-1 prefill scattered into the slot's cache rows
        # (one entry per distinct prompt length).
        self._prefill_bucket = jax.jit(self._prefill_bucket_impl,
                                       donate_argnums=(2,))
        self._prefill_one = jax.jit(self._prefill_impl, donate_argnums=(2,))
        # trace counts survive run_until_drained's stats reset: the jit cache
        # is a server-lifetime property (the compile-count regression test and
        # serve_bench read these directly).
        self.compiles: Dict[str, int] = {"prefill": 0, "decode": 0}
        self.stats: Dict[str, Any] = self._fresh_stats()

    @staticmethod
    def _fresh_stats() -> Dict[str, Any]:
        """Reset contract (enforced by test_obs): EVERY key in this dict is
        PER-DRAIN — :meth:`run_until_drained` replaces ``self.stats`` with a
        fresh copy at entry, so after a drain the dict describes that drain
        only (``pages_peak`` is the peak within the drain: the allocator's
        lifetime peak lives in ``alloc.peak_in_use``). Cumulative-across-
        drains state lives elsewhere, by design: ``compiles`` (jit cache is
        a server-lifetime property), the ``repro.obs`` metrics this class
        mirrors into (monotone counters/histograms in ``self.registry``),
        and the span ring in ``self.tracer``. Per-tick callers
        (:meth:`step` via the router) never reset anything."""
        return {"prefill_s": 0.0, "decode_s": 0.0, "steps": 0,
                "prefill_tokens": 0, "decode_tokens": 0,
                "prefill_dispatches": 0, "decode_dispatches": 0,
                "host_bytes_prefill": 0, "host_bytes_decode": 0,
                # paged-mode extras (zero in contiguous mode). Page-table
                # uploads get their OWN byte counter so the contiguous
                # host-bytes accounting keeps its exact per-dispatch formula.
                "host_bytes_page_tables": 0, "prefill_chunks": 0,
                "prefix_hit_tokens": 0, "cow_copies": 0,
                "pages_in_use": 0, "pages_peak": 0}

    # -- observability ------------------------------------------------------
    def set_obs_labels(self, labels: Dict[str, str]) -> None:
        """(Re)bind this server's metric children. Standalone servers carry
        ``{"replica": "solo"}``; the router rebinds each to its index."""
        self.obs_labels = dict(labels)
        r = self.registry
        rep = self.obs_labels.get("replica", "solo")
        lab = ("replica", "phase")
        self._m_dispatch = {
            p: r.counter("serve_dispatches_total",
                         "device dispatches", lab).labels(replica=rep,
                                                          phase=p)
            for p in ("prefill", "decode")}
        self._m_tokens = {
            p: r.counter("serve_tokens_total",
                         "tokens prefilled / decoded", lab).labels(
                             replica=rep, phase=p)
            for p in ("prefill", "decode")}
        self._m_dispatch_s = {
            p: r.histogram("serve_dispatch_seconds",
                           "wall time per device dispatch", lab).labels(
                               replica=rep, phase=p)
            for p in ("prefill", "decode")}
        self._m_compiles = {
            p: r.counter("serve_compiles_total",
                         "jit traces (server-lifetime, never reset)",
                         lab).labels(replica=rep, phase=p)
            for p in ("prefill", "decode")}
        self._m_host_bytes = {
            p: r.counter("serve_host_bytes_total",
                         "bytes crossing the device->host boundary", lab)
            .labels(replica=rep, phase=p)
            for p in ("prefill", "decode", "page_tables")}
        self._m_e2e = r.histogram(
            "serve_request_e2e_seconds", "submit -> done", ("replica",)
        ).labels(replica=rep)
        self._m_ttft = r.histogram(
            "serve_request_ttft_seconds", "submit -> first token",
            ("replica",)).labels(replica=rep)
        self._m_pages = r.gauge(
            "serve_pages_in_use", "page-pool pages currently referenced",
            ("replica",)).labels(replica=rep)
        self._m_prefix_hits = r.counter(
            "serve_prefix_hit_tokens_total",
            "prompt tokens skipped via prefix sharing", ("replica",)
        ).labels(replica=rep)
        self._m_cow = r.counter(
            "serve_cow_copies_total", "copy-on-write page copies",
            ("replica",)).labels(replica=rep)
        # sliding-window phase attribution (the SLO-facing latencies):
        # TTFT and per-token inter-token latency over the last
        # `obs_window_s` seconds, labeled by replica AND tier so a mixed
        # float/int8 fleet reads per-tier percentiles off one family
        wlab = ("replica", "tier")
        self._w_ttft = r.windowed_histogram(
            "serve_ttft_window_seconds",
            "submit -> first token, sliding window", wlab,
            window_s=self.obs_window_s, clock=self._clock
        ).labels(replica=rep, tier=self.tier)
        self._w_itl = r.windowed_histogram(
            "serve_itl_window_seconds",
            "per-token inter-token latency, sliding window", wlab,
            window_s=self.obs_window_s, clock=self._clock
        ).labels(replica=rep, tier=self.tier)

    def _end_req_span(self, rid: int, **attrs) -> None:
        span = self._req_spans.pop(rid, None)
        if span is not None:
            self.tracer.end(span, **attrs)

    def _start_wait(self, req: Request) -> None:
        root = self._req_spans.get(req.rid)
        self._wait_spans[req.rid] = self.tracer.start(
            "admit_wait", parent=None if root is None else root.sid,
            rid=str(req.rid))

    def _end_wait(self, rid: int, t: Optional[float] = None,
                  **attrs) -> None:
        """End ``rid``'s ``admit_wait`` span, at ``t`` if given."""
        span = self._wait_spans.pop(rid, None)
        if span is not None:
            span.t1 = t
            self.tracer.end(span, **attrs)

    # -- quantized decode mode / mesh scope --------------------------------
    def _gemm_scope(self):
        """Trace/serving-time scope around every dispatch: the GEMM provider
        (FFIP int8 when quantized) plus, under ``mesh=``, the ambient dist
        mesh so tuned-flash shard_map and NamedSharding resolution engage at
        trace time."""
        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(dist_context.mesh_context(self.mesh))
        if self._gemm_cfg is not None:
            stack.enter_context(use_gemm(self._gemm_cfg))
        return stack

    def _params_for(self, params):
        """Resolve the run-ready param tree for a dispatch.

        Preference order: an injected ``prepared`` artifact (warm start —
        zero re-quantization/re-encode, `repro.prepare`'s counters prove it);
        else, when a GEMM config is active, a `prepare.prepare_lm` tree built
        once per distinct params object (the former private attach path,
        now a thin wrapper over repro.prepare); else the float params as-is.
        Under ``mesh=`` the result is placed through dist.param_specs once
        per distinct tree."""
        if self.prepared is not None:
            p = self.prepared.params
        elif self._gemm_cfg is None:
            p = params
        else:
            if self._qparams_src is not params:
                from repro import prepare
                self._qparams = prepare.prepare_lm(
                    params, quantized=True, y_deltas=False).params
                self._qparams_src = params
            p = self._qparams
        if self.mesh is not None:
            if self._placed_src is not p:
                specs = dist_sharding.param_specs(
                    p, self.mesh, moe_partition=self.moe_partition)
                self._placed = jax.device_put(
                    p, dist_sharding.to_named(specs, self.mesh))
                self._placed_src = p
            p = self._placed
        return p

    # -- device programs ---------------------------------------------------
    def _dispatch(self, phase: str, program, params, x, *rest,
                  sync: bool = True):
        """Run ``program(params, x, cache, *rest)`` on the server's cache
        under the GEMM scope (``<phase>.dispatch`` span) and, with ``sync``,
        bring its token output to the host (``<phase>.sync``: the host
        blocked on the device). Returns that output (None without ``sync``)
        and the wall seconds the two took."""
        t0 = self._clock()
        with self.tracer.span(phase + ".dispatch"), self._gemm_scope():
            self.cache, out = program(params, x, self.cache, *rest)
        out_h = None
        if sync:
            with self.tracer.span(phase + ".sync"):
                out_h = np.asarray(jax.device_get(out))
        return out_h, self._clock() - t0

    def _decode_impl(self, params, last, cache, pos, live, rem, eos):
        self.compiles["decode"] += 1    # side effect runs at trace time only
        self._m_compiles["decode"].inc()
        return self.model.sample_steps(params, last, cache, pos, live, rem,
                                       eos, steps=self.decode_chunk)

    def _prefill_bucket_impl(self, params, tokens, cache, lengths, mask):
        self.compiles["prefill"] += 1   # once per bucket length
        self._m_compiles["prefill"].inc()
        return self.model.prefill_sample(params, tokens, cache, lengths, mask)

    def _prefill_impl(self, params, tokens, cache, slot_idx):
        # fallback (SSM/hybrid/enc-dec caches): run a batch-1 forward and
        # scatter its cache rows into slot_idx; argmax fused on device.
        self.compiles["prefill"] += 1   # once per distinct prompt length
        self._m_compiles["prefill"].inc()
        one_cache = self.model.init_cache(1, self.max_len)
        new_one, logits = self.model.prefill(params, tokens, one_cache)

        def put(full, one, axis):
            idx = [slice(None)] * full.ndim
            idx[axis] = slot_idx
            return full.at[tuple(idx)].set(
                one.squeeze(axis=axis).astype(full.dtype))

        cache = jax.tree.map(put, cache, new_one, self._batch_axes)
        return cache, jnp.argmax(logits[0]).astype(jnp.int32)

    def _decode_paged_impl(self, params, last, cache, pos, live, rem, eos,
                           page_table):
        self.compiles["decode"] += 1
        self._m_compiles["decode"].inc()
        return self.model.sample_steps(
            params, last, cache, pos, live, rem, eos,
            steps=self.decode_chunk, page_table=page_table,
            paged_impl=self.paged_attention)

    def _prefill_chunk_impl(self, params, tokens, cache, page_table, offset,
                            valid_len, write_start):
        self.compiles["prefill"] += 1   # one entry total: fixed chunk width
        self._m_compiles["prefill"].inc()
        return self.model.prefill_chunk_paged(
            params, tokens, cache, page_table, offset, valid_len,
            write_start, paged_impl=self.paged_attention)

    # -- prefill -----------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        b = _MIN_BUCKET
        while b < n:
            b *= 2
        return min(b, self.max_len)

    @staticmethod
    def cache_rows(prompt_len: int, max_new_tokens: int) -> int:
        """Cache rows a request can ever occupy. The prompt takes
        ``prompt_len`` rows; each DECODE STEP writes one more — and the final
        sampled token is emitted without a step following it, so it never
        writes a row. ``max_new_tokens`` new tokens therefore need only
        ``max_new_tokens - 1`` rows beyond the prompt (paged admission sizes
        its page reservation from the same formula)."""
        return prompt_len + max(max_new_tokens, 1) - 1

    @staticmethod
    def _req_key(req: Request) -> tuple:
        """Payload identity for idempotent rids: same rid MUST mean same
        work, or the cached-completion contract would silently lie."""
        return (np.asarray(req.prompt, np.int64).tobytes(),
                int(req.max_new_tokens), int(req.eos_id))

    def _find_inflight(self, rid: int) -> Optional[Request]:
        for r in self._queue:
            if r.rid == rid:
                return r
        for s in self.slots:
            if s.req is not None and s.req.rid == rid:
                return s.req
        return None

    def submit(self, req: Request):
        rows = self.cache_rows(len(req.prompt), req.max_new_tokens)
        if rows > self.max_len:
            raise AdmissionImpossibleError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) needs {rows} cache "
                f"rows (the last sampled token is never written) but "
                f"max_len is {self.max_len}")
        if self.paged:
            # fail fast at SUBMIT time: worst-case pages beyond the whole
            # pool can never be admitted no matter how many slots drain.
            pages = -(-rows // self.page_size)
            if pages > self.num_pages:
                raise AdmissionImpossibleError(
                    f"request {req.rid}: needs {pages} pages worst-case "
                    f"({rows} rows / page_size {self.page_size}) but the "
                    f"pool holds only {self.num_pages}")
        req.t_submit = self._clock()
        key = self._req_key(req)
        inflight = self._find_inflight(req.rid)
        if inflight is not None:
            if self._req_key(inflight) != key:
                raise AdmissionImpossibleError(
                    f"rid {req.rid} resubmitted with a different "
                    f"prompt/budget while the original is in flight")
            req.out_tokens = []
            self._dup_waiters.setdefault(req.rid, []).append(req)
            return
        hit = self._results.get(req.rid)
        if hit is not None:
            hkey, toks = hit
            if hkey != key:
                raise AdmissionImpossibleError(
                    f"rid {req.rid} resubmitted with a different "
                    f"prompt/budget than its cached completion")
            req.out_tokens = list(toks)
            req.t_first = req.t_done = self._clock()
            self.tracer.event("request", rid=str(req.rid), cached=True)
            self._cached_hits.append(req)
            return
        req.out_tokens = []
        req.itl_s = []
        if self.trace_requests and req.rid not in self._req_spans:
            self._req_spans[req.rid] = self.tracer.start(
                "request", rid=str(req.rid), prompt=len(req.prompt),
                max_new_tokens=req.max_new_tokens)
        self._start_wait(req)
        self._queue.append(req)

    def has_queued(self) -> bool:
        return bool(self._queue)

    def _finish(self, req: Request):
        req.t_done = self._clock()
        self._m_e2e.observe(req.t_done - req.t_submit)
        if req.t_first:
            self._m_ttft.observe(req.t_first - req.t_submit)
        self._end_req_span(req.rid, tokens=len(req.out_tokens))
        self._completed.append(req)
        self._results[req.rid] = (self._req_key(req), list(req.out_tokens))
        self._results.move_to_end(req.rid)
        while len(self._results) > self._result_cache_size:
            self._results.popitem(last=False)
        for w in self._dup_waiters.pop(req.rid, []):
            w.out_tokens = list(req.out_tokens)
            w.itl_s = None if req.itl_s is None else list(req.itl_s)
            w.t_first = req.t_first
            w.t_done = req.t_done
            self._completed.append(w)

    def take_completed(self) -> List[Request]:
        """Drain the completion list (the router's per-tick collection path;
        run_until_drained keeps accumulating instead)."""
        done, self._completed = self._completed, []
        return done

    def abort(self, rid: int) -> bool:
        """Remove a request wherever it lives — queue, slot, or the
        idempotency cache — releasing every resource it held. A paged
        request's pages are decref'd and its admission reservation is
        returned (the ledger drains to 0), with prefix pages published only
        up to the rows actually COMPUTED, so an aborted prefill never
        poisons the prefix index. The cached result (if any) is dropped too:
        after an abort, a resubmitted rid recomputes from scratch. Returns
        True if anything was removed."""
        found = self._results.pop(rid, None) is not None
        for i, r in enumerate(self._queue):
            if r.rid == rid:
                del self._queue[i]
                found = True
                break
        else:
            for slot in self.slots:
                if slot.req is not None and slot.req.rid == rid:
                    if slot.seq is not None:
                        self._release_seq(slot, upto=slot.seq.filled)
                    slot.req = None
                    slot.pos = 0
                    slot.remaining = 0
                    found = True
                    break
        # duplicates that were waiting on the aborted original become
        # first-class queued requests (their payload is identical).
        for w in self._dup_waiters.pop(rid, []):
            self._queue.appendleft(w)
        if found:
            self._end_wait(rid, aborted=True)
            self._end_req_span(rid, aborted=True)
        return found

    # -- router-facing load/health introspection ---------------------------
    def free_slots(self) -> int:
        return sum(1 for s in self.slots if s.req is None)

    def outstanding_rows(self) -> int:
        """Worst-case cache rows committed to requests this server holds
        (slots + internal queue) — the router's least-loaded metric."""
        rows = 0
        for s in self.slots:
            if s.req is not None:
                rows += self.cache_rows(len(s.req.prompt),
                                        s.req.max_new_tokens)
        for r in self._queue:
            rows += self.cache_rows(len(r.prompt), r.max_new_tokens)
        return rows

    def page_headroom(self) -> Optional[int]:
        """Upper bound on pages a NEW request could still claim (free pages
        minus outstanding reservations, plus prefix-index entries that
        admission may evict). None in contiguous mode."""
        if not self.paged:
            return None
        return self.alloc.free_count - self._reserved + len(self.prefix)

    def request_phase(self, rid: int) -> Optional[str]:
        """'queued' | 'prefilling' | 'decoding' for an inflight rid, None if
        unknown (completed or never submitted). Contiguous prefill is atomic
        inside a step, so contiguous requests are never seen 'prefilling'."""
        for r in self._queue:
            if r.rid == rid:
                return "queued"
        for s in self.slots:
            if s.req is not None and s.req.rid == rid:
                if s.seq is not None and s.seq.compute_next < s.seq.n:
                    return "prefilling"
                return "decoding"
        return None

    def _place(self, slot_i: int, req: Request, first: int):
        """Post-prefill bookkeeping shared by all prefill paths."""
        req.out_tokens.append(first)
        req.t_first = self._clock()
        self._w_ttft.observe(req.t_first - req.t_submit)
        slot = self.slots[slot_i]
        if req.max_new_tokens <= 1 or first == req.eos_id:
            # finished at prefill (token budget of 1, or EOS on the first
            # token): releases the slot immediately — admission keeps going.
            self._finish(req)
            if slot.seq is not None:
                self._release_seq(slot)
            slot.req = None
            return
        slot.req = req
        slot.pos = len(req.prompt)   # prompt rows in cache; the first
        slot.remaining = req.max_new_tokens - 1   # generated token is in
        # flight and will be written at row `pos` by the next decode step
        slot.t_tok = req.t_first

    def _admit(self, params):
        if self.paged:
            self._admit_paged()
            return
        while self._queue:
            free = [i for i, s in enumerate(self.slots) if s.req is None]
            if not free:
                return
            if self._bucketed:
                self._admit_bucket(params, free)
            else:
                self._admit_one(params, free[0])

    def _admit_bucket(self, params, free: List[int]):
        """One batched prefill dispatch: the head-of-queue request's bucket,
        plus every queued request (FIFO) sharing that bucket, up to the free
        slot count. Other buckets stay queued in order for the next round.

        The dispatch always runs the forward over all B slot rows (masked-out
        rows are discarded), trading up to B× redundant prefill FLOPs on a
        single-request admission for a jit cache keyed ONLY by bucket length
        — O(log max_len) compiles total instead of O(buckets × batch sizes).
        Under load the dispatch carries several requests and the waste
        amortizes away; latency-sensitive single-stream serving can set
        ``prefill_buckets=False`` to get the batch-1 fallback."""
        bucket = self._bucket_len(len(self._queue[0].prompt))
        batch: List[Request] = []
        kept: List[Request] = []
        while self._queue and len(batch) < len(free):
            r = self._queue.popleft()
            if self._bucket_len(len(r.prompt)) == bucket:
                batch.append(r)
            else:
                kept.append(r)
        self._queue.extendleft(reversed(kept))

        n_tokens = sum(len(r.prompt) for r in batch)
        with self.tracer.span("prefill", bucket=bucket,
                              rids=[r.rid for r in batch],
                              tokens=n_tokens) as span:
            with self.tracer.span("prefill.pack"):
                tokens = np.zeros((self.b, bucket), np.int32)
                lengths = np.ones((self.b,), np.int32)
                mask = np.zeros((self.b,), bool)
                for slot_i, req in zip(free, batch):
                    n = len(req.prompt)
                    tokens[slot_i, :n] = req.prompt
                    lengths[slot_i] = n
                    mask[slot_i] = True
                args = (jnp.asarray(tokens), jnp.asarray(lengths),
                        jnp.asarray(mask))
            for req in batch:
                self._end_wait(req.rid, span.span.t0)
            first_h, dt = self._dispatch("prefill", self._prefill_bucket,
                                         params, *args)   # (B,) int32
        self.stats["prefill_s"] += dt
        self.stats["prefill_tokens"] += n_tokens
        self.stats["prefill_dispatches"] += 1
        self.stats["host_bytes_prefill"] += int(first_h.nbytes)
        self._m_dispatch["prefill"].inc()
        self._m_dispatch_s["prefill"].observe(dt)
        self._m_tokens["prefill"].inc(n_tokens)
        self._m_host_bytes["prefill"].inc(int(first_h.nbytes))
        with self.tracer.span("place"):
            for slot_i, req in zip(free, batch):
                self._place(slot_i, req, int(first_h[slot_i]))

    def _admit_one(self, params, slot_i: int):
        req = self._queue.popleft()
        with self.tracer.span("prefill", rid=str(req.rid),
                              tokens=len(req.prompt)) as span:
            with self.tracer.span("prefill.pack"):
                toks = jnp.asarray(req.prompt, jnp.int32)[None, :]
            self._end_wait(req.rid, span.span.t0)
            first_h, dt = self._dispatch("prefill", self._prefill_one,
                                         params, toks, slot_i)
        self.stats["prefill_s"] += dt
        self.stats["prefill_tokens"] += len(req.prompt)
        self.stats["prefill_dispatches"] += 1
        self.stats["host_bytes_prefill"] += 4
        self._m_dispatch["prefill"].inc()
        self._m_dispatch_s["prefill"].observe(dt)
        self._m_tokens["prefill"].inc(len(req.prompt))
        self._m_host_bytes["prefill"].inc(4)
        with self.tracer.span("place"):
            self._place(slot_i, req, int(first_h))

    # -- paged mode --------------------------------------------------------
    def _admit_paged(self):
        """Admission is pure host bookkeeping in paged mode — no device work.
        The prompt runs later, one page-aligned chunk per :meth:`step`, via
        :meth:`_prefill_tick`. Strict FIFO: a head-of-queue request that
        cannot reserve its worst-case pages blocks the queue (it will fit
        once running requests release pages)."""
        while self._queue:
            free = [i for i, s in enumerate(self.slots) if s.req is None]
            if not free:
                return
            if not self._try_admit_paged(free[0], self._queue[0]):
                if (all(s.req is None for s in self.slots)
                        and not len(self.prefix)):
                    req = self._queue[0]
                    raise RuntimeError(
                        f"request {req.rid} needs more pages than the pool "
                        f"holds ({self.alloc.num_pages}) even with every "
                        f"slot idle — raise num_pages or lower "
                        f"max_new_tokens")
                return
            self._queue.popleft()

    def _try_admit_paged(self, slot_i: int, req: Request) -> bool:
        """Plan a request: attach shared prefix pages from the index
        (refcounted), then reserve worst-case fresh pages — evicting LRU
        index entries under pressure. All-or-nothing: on failure every
        attached page is released and the queue head stays put."""
        ps = self.page_size
        n = len(req.prompt)
        pages_needed = -(-self.cache_rows(n, req.max_new_tokens) // ps)
        keys = page_keys(req.prompt, ps) if self.prefix_sharing else []
        pkey = partial_key(req.prompt, ps) if self.prefix_sharing else None
        attached: List[int] = []
        hit = 0
        shared_tail = False
        for k in keys:                       # walk stops at the first miss:
            page = self.prefix.get(k)        # chained keys make any later
            if page is None:                 # match impossible
                break
            self.alloc.incref(page)
            attached.append(page)
            hit += ps
        if pkey is not None and len(attached) == len(keys):
            page = self.prefix.get(pkey)
            if page is not None:             # whole-prompt match incl. tail
                self.alloc.incref(page)
                attached.append(page)
                shared_tail = True
                hit = n
        # Worst-case fresh pages: everything not attached, plus one COW copy
        # if the shared tail page will be decoded into (first decode step
        # writes row n, which lives in the tail page).
        worst = (pages_needed - len(attached)
                 + (1 if shared_tail and req.max_new_tokens > 1 else 0))
        while (self.alloc.free_count - self._reserved < worst
               and len(self.prefix)):
            self.prefix.evict_lru(1)
        if self.alloc.free_count - self._reserved < worst:
            for p in attached:
                self.alloc.decref(p)
            return False
        self._reserved += worst
        self.stats["prefix_hit_tokens"] += hit
        if hit:
            self._m_prefix_hits.inc(hit)
        seq = _PagedSeq(
            n=n, pages=attached, keys=keys, pkey=pkey, filled=hit,
            # a fully shared prompt still recomputes its LAST token: the
            # first sampled token needs that hidden state (writes nothing —
            # write_start == n covers no rows).
            compute_next=min(hit, n - 1), shared_tail=shared_tail,
            reserve=worst, registered=min(len(attached), len(keys)))
        slot = self.slots[slot_i]
        slot.req = req
        slot.seq = seq
        slot.pos = 0
        slot.remaining = 0               # set by _place on the final chunk
        return True

    def _alloc_page(self, seq: _PagedSeq) -> int:
        page = self.alloc.alloc()
        assert seq.reserve > 0, "page allocated beyond admission reservation"
        seq.reserve -= 1
        self._reserved -= 1
        return page

    def _ensure_pages(self, slot: _Slot, first_row: int, end_row: int):
        """Make rows [first_row, end_row) WRITABLE: allocate missing pages
        and copy-on-write any shared page in the range (refcount > 1 means
        the prefix index and/or another sequence still reads it)."""
        if first_row >= end_row:
            return
        seq = slot.seq
        ps = self.page_size
        for li in range(first_row // ps, -(-end_row // ps)):
            if li >= len(seq.pages):
                seq.pages.append(self._alloc_page(seq))
            elif self.alloc.refcount(seq.pages[li]) > 1:
                old = seq.pages[li]
                new = self._alloc_page(seq)
                self.cache = self._copy_page(
                    self.cache, jnp.asarray(old, jnp.int32),
                    jnp.asarray(new, jnp.int32))
                self.alloc.decref(old)
                seq.pages[li] = new
                self.stats["cow_copies"] += 1
                self._m_cow.inc()

    def _register_prefix(self, seq: _PagedSeq, upto_rows: int):
        """Publish every FULL prompt page whose rows are all filled."""
        if not self.prefix_sharing:
            return
        while (seq.registered < len(seq.keys)
               and (seq.registered + 1) * self.page_size <= upto_rows):
            self.prefix.register(seq.keys[seq.registered],
                                 seq.pages[seq.registered])
            seq.registered += 1

    def _release_seq(self, slot: _Slot, *, upto: Optional[int] = None):
        """Drop a finished request's page references. Prompt pages stay
        resident through the prefix index (which holds its own reference)
        until LRU eviction; the terminal partial page is published here —
        keyed by the whole prompt — so an identical prompt resubmitted later
        skips prefill entirely. ``upto`` caps publication at the prompt rows
        actually computed (an ABORTED prefill publishes only its finished
        pages — rows past ``seq.filled`` were never written)."""
        seq = slot.seq
        upto = seq.n if upto is None else min(upto, seq.n)
        self._register_prefix(seq, upto)
        tail_li = seq.n // self.page_size
        if (self.prefix_sharing and seq.pkey is not None and upto >= seq.n
                and len(seq.pages) > tail_li):
            self.prefix.register(seq.pkey, seq.pages[tail_li])
        for p in seq.pages:
            self.alloc.decref(p)
        self._reserved -= seq.reserve
        seq.reserve = 0
        slot.seq = None

    def _prefill_tick(self, params) -> int:
        """Dispatch at most ONE page-aligned prefill chunk per mid-prefill
        slot, then return — the caller's decode dispatch runs next, so a
        long prompt admits without stalling already-active slots for more
        than one chunk's latency. Returns the number of chunks dispatched."""
        work = 0
        chunk = self.prefill_chunk
        for slot_i, slot in enumerate(self.slots):
            seq = slot.seq
            if slot.req is None or seq is None or seq.compute_next >= seq.n:
                continue
            start = seq.compute_next
            end = min(seq.n, (start // chunk + 1) * chunk)
            last_chunk = end >= seq.n        # token only meaningful here
            with self.tracer.span("prefill_chunk", rid=str(slot.req.rid),
                                  rid_int=slot.req.rid, start=start,
                                  end=end) as span:
                with self.tracer.span("prefill.pack"):
                    self._ensure_pages(slot, max(start, seq.filled), end)
                    tokens = np.zeros((1, chunk), np.int32)
                    tokens[0, :end - start] = slot.req.prompt[start:end]
                    pt = np.zeros((1, self.max_pages), np.int32)
                    pt[0, :len(seq.pages)] = seq.pages
                    args = (jnp.asarray(tokens), jnp.asarray(pt),
                            jnp.asarray(start, jnp.int32),
                            jnp.asarray(end - start, jnp.int32),
                            jnp.asarray(seq.filled, jnp.int32))
                self._end_wait(slot.req.rid, span.span.t0)
                tok_h, dt = self._dispatch("prefill", self._prefill_chunk_fn,
                                           params, *args, sync=last_chunk)
            if last_chunk:
                self.stats["host_bytes_prefill"] += 4
                self._m_host_bytes["prefill"].inc(4)
            self.stats["prefill_s"] += dt
            self.stats["prefill_tokens"] += end - start
            self.stats["prefill_dispatches"] += 1
            self.stats["prefill_chunks"] += 1
            self.stats["host_bytes_page_tables"] += int(pt.nbytes)
            self._m_dispatch["prefill"].inc()
            self._m_dispatch_s["prefill"].observe(dt)
            self._m_tokens["prefill"].inc(end - start)
            self._m_host_bytes["page_tables"].inc(int(pt.nbytes))
            seq.compute_next = end
            seq.filled = max(seq.filled, end)
            self._register_prefix(seq, seq.filled)
            work += 1
            if last_chunk:
                with self.tracer.span("place"):
                    self._place(slot_i, slot.req, int(tok_h))
        return work

    def _refresh_page_stats(self):
        self.stats["pages_in_use"] = self.alloc.in_use
        self.stats["pages_peak"] = self.alloc.peak_in_use
        self._m_pages.set(self.alloc.in_use)

    # -- decode ------------------------------------------------------------
    def step(self, params) -> int:
        """One fused decode dispatch (``decode_chunk`` lockstep steps) over
        all active slots; in paged mode, preceded by at most one prefill
        CHUNK per mid-prefill slot (chunked prefill interleaves with decode
        instead of stalling it). Returns #active decode slots plus #prefill
        chunks dispatched.

        Traced as a ``step`` span with children ``params``, ``admit``
        (``prefill`` / ``prefill_chunk`` with ``.pack`` / ``.dispatch`` /
        ``.sync`` below, and ``place``), ``decode`` (``.pack`` /
        ``.dispatch`` / ``.sync``) and ``replay``."""
        with self.tracer.span("step", queued=len(self._queue),
                              active=self.b - self.free_slots()):
            if self._cached_hits:   # idempotent duplicates: cached results
                self._completed.extend(self._cached_hits)
                self._cached_hits.clear()
            with self.tracer.span("params"):
                params = self._params_for(params)
            with self.tracer.span("admit"):
                self._admit(params)
                prefill_work = self._prefill_tick(params) if self.paged else 0
            # mid-prefill paged slots hold remaining == 0 and sit out the
            # decode dispatch; contiguous occupancy always implies
            # remaining >= 1.
            active = [i for i, s in enumerate(self.slots)
                      if s.req is not None and s.remaining > 0]
            if active:
                toks_h, t_host = self._decode_dispatch(params, active)
                with self.tracer.span("replay") as span:
                    span.set(emitted=self._replay(active, toks_h, t_host))
            if self.paged:
                self._refresh_page_stats()
            return len(active) + prefill_work

    def _decode_dispatch(self, params, active: List[int]):
        """The decode dispatch over the ``active`` slots. Returns the
        ``(chunk, B)`` sampled tokens on the host and when they got there.

        Its ``decode`` span counts ``live_rows``, the K/V rows the active
        slots attend over the chunk's steps (``pos + 1`` at the first, up to
        each slot's budget), against ``cache_rows``, the rows the program's
        cache operand holds (slots x max_len, or the page pool) times the
        chunk."""
        k = self.decode_chunk
        live_rows = 0
        for i in active:
            slot = self.slots[i]
            n = min(k, slot.remaining)
            live_rows += n * (slot.pos + 1) + n * (n - 1) // 2
        rows = (self.num_pages * self.page_size if self.paged
                else self.b * self.max_len)
        with self.tracer.span("decode",
                              rids=[self.slots[i].req.rid for i in active],
                              chunk=k, live_rows=live_rows,
                              cache_rows=k * rows):
            with self.tracer.span("decode.pack"):
                last = np.zeros((self.b,), np.int32)
                pos = np.zeros((self.b,), np.int32)
                live = np.zeros((self.b,), bool)
                rem = np.zeros((self.b,), np.int32)
                eos = np.full((self.b,), -1, np.int32)
                for i in active:
                    slot = self.slots[i]
                    last[i] = slot.req.out_tokens[-1]
                    pos[i] = slot.pos
                    live[i] = True
                    rem[i] = slot.remaining
                    eos[i] = slot.req.eos_id
                # per-slot position vector: slot i writes KV at row pos[i]
                # and masks rows >= pos[i] + 1; inactive/frozen slots
                # re-write their own row with unchanged values, so the cache
                # stays bit-identical to sequential decode across the whole
                # chunk. (Paged mode instead GATES frozen slots' writes off —
                # pool rows can be shared.)
                args = [jnp.asarray(last), jnp.asarray(pos),
                        jnp.asarray(live), jnp.asarray(rem), jnp.asarray(eos)]
                if self.paged:
                    for i in active:
                        slot = self.slots[i]
                        self._ensure_pages(slot, slot.pos,
                                           slot.pos + min(k, slot.remaining))
                    pt = np.zeros((self.b, self.max_pages), np.int32)
                    for i in active:
                        seq = self.slots[i].seq
                        pt[i, :len(seq.pages)] = seq.pages
                    args.append(jnp.asarray(pt))
                    self.stats["host_bytes_page_tables"] += int(pt.nbytes)
                    self._m_host_bytes["page_tables"].inc(int(pt.nbytes))
            toks_h, dt = self._dispatch(
                "decode", self._decode_paged if self.paged else self._decode,
                params, *args)                          # (chunk, B) int32
        t_host = self._clock()
        self.stats["decode_s"] += dt
        self.stats["decode_dispatches"] += 1
        self.stats["host_bytes_decode"] += int(toks_h.nbytes)
        self._m_dispatch["decode"].inc()
        self._m_dispatch_s["decode"].observe(dt)
        self._m_host_bytes["decode"].inc(int(toks_h.nbytes))
        return toks_h, t_host

    def _replay(self, active: List[int], toks_h: np.ndarray,
                t_host: float) -> int:
        """Replay the device's (eos, remaining) bookkeeping on the host to
        recover which of the chunk's tokens each slot emitted; finish the
        requests that ended. Returns the tokens emitted."""
        got = dict.fromkeys(active, 0)
        total = 0
        for j in range(toks_h.shape[0]):
            emitted = 0
            for i in active:
                slot = self.slots[i]
                if slot.req is None:
                    continue
                nxt = int(toks_h[j, i])
                slot.req.out_tokens.append(nxt)
                slot.pos += 1
                slot.remaining -= 1
                got[i] += 1
                emitted += 1
                if slot.remaining <= 0 or nxt == slot.req.eos_id:
                    self._charge_itl(slot, got[i], t_host)
                    self._finish(slot.req)
                    if slot.seq is not None:
                        self._release_seq(slot)
                    slot.req = None   # freed -> next _admit refills it
            if emitted:
                self.stats["steps"] += 1
                self.stats["decode_tokens"] += emitted
                self._m_tokens["decode"].inc(emitted)
                total += emitted
        for i in active:
            if self.slots[i].req is not None:
                self._charge_itl(self.slots[i], got[i], t_host)
        return total

    def _charge_itl(self, slot: _Slot, k: int, t_host: float) -> None:
        """Charge the ``k`` tokens a slot's request just received the time
        since its previous token reached the host, in equal shares."""
        gap = (t_host - slot.t_tok) / k
        slot.t_tok = t_host
        for _ in range(k):
            self._w_itl.observe(gap)
            if slot.req.itl_s is not None:
                slot.req.itl_s.append(gap)

    def run_until_drained(self, params, *, max_steps: int = 10_000,
                          ) -> List[Request]:
        """Step until the queue and all slots drain. Returns the finished
        requests in COMPLETION order — including requests admitted and
        completed within a single step (e.g. max_new_tokens=1). ``stats``
        describe this run only (reset here alongside the completion list);
        ``compiles`` is server-lifetime and is NOT reset.

        Hitting ``max_steps`` with requests still live raises a typed
        :class:`ServeStallError` listing every stuck request id and where it
        was wedged (queued, or its slot's position/budget) — a frozen queue
        surfaces loudly instead of returning a silently short list."""
        self._completed = []
        self.stats = self._fresh_stats()
        for _ in range(max_steps):
            if self.step(params) == 0 and not self._queue:
                break
        else:
            stuck: Dict[int, str] = {}
            for r in self._queue:
                stuck[r.rid] = "queued (never admitted)"
            for i, s in enumerate(self.slots):
                if s.req is not None:
                    phase = self.request_phase(s.req.rid) or "decoding"
                    stuck[s.req.rid] = (f"slot {i} ({phase}): pos={s.pos} "
                                        f"remaining={s.remaining}")
            if stuck:
                raise ServeStallError(
                    f"run_until_drained hit max_steps={max_steps} with "
                    f"{len(stuck)} request(s) still live", stuck=stuck)
        return self._completed
