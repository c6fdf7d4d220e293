"""Attention variants: GQA (opt. sliding-window / local:global), MLA
(DeepSeek-V2), and cross-attention (enc-dec). All projections go through the
GEMM provider; score/context matmuls are activation-activation products (out
of FIP scope — the paper's technique targets weight GEMMs on the MXU).

Window convention: ``window`` is a (possibly traced) int32 scalar; 0 means
full attention. Traced windows let a scan-over-layers carry per-layer
local/global patterns (gemma3 5:1) without unrolling the stack.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import layers as L

Array = jax.Array
NEG_INF = -2.0e38


def gqa_init(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, d, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": L.dense_init(k2, d, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": L.dense_init(k3, d, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": L.dense_init(k4, cfg.n_heads * hd, d, dtype),
    }


def _cache_write(buf: Array, new: Array, cache_pos,
                 write_mask: Optional[Array] = None) -> Array:
    """Write ``new`` (B, s, ...) rows into ``buf`` (B, S_max, ...) at
    ``cache_pos``.

    Scalar ``cache_pos``: shared offset (prefill / legacy decode) — a single
    dynamic slice. ``(B,)`` vector: per-slot offsets (continuous-batching
    decode) — one dynamic slice per batch row via vmap, lowering to a batched
    scatter. Slot i's row lands at ``buf[i, cache_pos[i]]``.

    ``write_mask`` (optional, (B,) bool): rows with a False mask keep their
    existing cache content — the bucketed batched prefill runs a full-width
    forward straight over the SHARED slot cache and only commits the rows
    being admitted, so live slots decoding next door are untouched. The
    masked form still lowers to one dynamic_update_slice per leaf (the slice
    is re-read, selected, and written back), never a per-leaf scatter.
    """
    new = new.astype(buf.dtype)
    pos = jnp.asarray(cache_pos, jnp.int32)
    if pos.ndim == 0:
        if write_mask is not None:
            cur = jax.lax.dynamic_slice_in_dim(buf, pos, new.shape[1], axis=1)
            keep = write_mask.reshape((-1,) + (1,) * (new.ndim - 1))
            new = jnp.where(keep, new, cur)
        return jax.lax.dynamic_update_slice_in_dim(buf, new, pos, axis=1)

    def one(row, n, p, m=None):
        if m is not None:
            cur = jax.lax.dynamic_slice_in_dim(row, p, n.shape[0], axis=0)
            n = jnp.where(m, n, cur)
        return jax.lax.dynamic_update_slice_in_dim(row, n, p, axis=0)

    if write_mask is not None:
        return jax.vmap(one)(buf, new, pos, write_mask)
    return jax.vmap(one)(buf, new, pos)


def _paged_write(pool: Array, new: Array, page_table: Array, cache_pos,
                 write_mask: Optional[Array] = None) -> Array:
    """Scatter ``new`` (B, s, ...) token rows into the page ``pool``
    (P, ps, ...) at logical positions ``cache_pos`` via the page table.

    ``page_table`` is (B, max_pages) int32 pool page ids; token ``t`` of
    sequence ``b`` lands in pool row ``page_table[b, t // ps] * ps + t % ps``.
    ``cache_pos``: scalar or (B,) first logical position of ``new``.
    ``write_mask``: None, (B,) or (B, s) bool — False rows are DROPPED (their
    scatter index is pushed out of range and ``mode="drop"`` discards it), so
    frozen/inactive slots never touch the shared pool. Rows whose logical
    position falls beyond the page table are likewise dropped.
    """
    new = new.astype(pool.dtype)
    n_pages, ps = pool.shape[:2]
    b, s = new.shape[:2]
    max_pages = page_table.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32).reshape(-1), (b,))
    r = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]      # (B, s)
    page = jnp.take_along_axis(page_table.astype(jnp.int32),
                               jnp.minimum(r // ps, max_pages - 1), axis=1)
    rows = page * ps + r % ps
    rows = jnp.where(r // ps < max_pages, rows, n_pages * ps)
    if write_mask is not None:
        wm = write_mask if write_mask.ndim == 2 else write_mask[:, None]
        rows = jnp.where(wm, rows, n_pages * ps)
    flat = pool.reshape((n_pages * ps,) + pool.shape[2:])
    flat = flat.at[rows.reshape(-1)].set(
        new.reshape((b * s,) + new.shape[2:]), mode="drop")
    return flat.reshape(pool.shape)


def _paged_view(pool: Array, page_table: Array) -> Array:
    """Gather pool pages into a (B, max_pages*ps, ...) contiguous view.

    With ``max_pages * ps == max_len`` the view has the contiguous cache's
    exact shape, so the downstream score/softmax/context math (and therefore
    the sampled tokens) is bit-identical to the contiguous-slot path —
    garbage in unallocated pages is masked by the caller's validity mask.
    """
    n_pages, ps = pool.shape[:2]
    b, max_pages = page_table.shape
    flat = pool.reshape((n_pages * ps,) + pool.shape[2:])
    rows = (page_table.astype(jnp.int32)[:, :, None] * ps
            + jnp.arange(ps, dtype=jnp.int32)[None, None, :])
    return flat[rows.reshape(b, max_pages * ps)]


def _cache_end(cache_pos, s: int) -> Array:
    """Exclusive end of valid cache rows per batch entry: (1, 1) for a shared
    scalar position, (B, 1) for per-slot positions — broadcasts against a
    (B or 1, S_max) key-position grid."""
    pos = jnp.asarray(cache_pos, jnp.int32)
    return jnp.reshape(pos + s, (-1, 1))


def _mask(q_pos: Array, k_pos: Array, window, causal: bool) -> Array:
    """(..., Sq, Sk) boolean keep-mask from positions + window scalar."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    keep = (diff >= 0) if causal else jnp.ones_like(diff, dtype=bool)
    windowed = jnp.logical_and(keep, diff < jnp.maximum(window, 1))
    return jnp.where(window > 0, windowed, keep)


def _flash_schedule(dtype, bh: int, sq: int, sk: int, d: int):
    """Flash block sizes + interpret mode from the ambient GEMM config.

    ``GemmConfig(block="auto")`` gives flash attention the same tuned-schedule
    treatment as the GEMM kernels: a trace-time lookup in the repro.tune
    cache for this shape bucket, defaults on a miss. ``interpret=None``
    passes backend auto-detection down to the kernel."""
    from repro.core.gemm import current_config
    cfg = current_config()
    bq, bk = 128, 128
    if cfg.block == "auto":
        from repro import tune
        got = tune.lookup_flash_blocks(dtype, bh, sq, sk, d)
        if got is not None:
            bq, bk = got
    return bq, bk, cfg.interpret


def _flash_sdpa(q: Array, k: Array, v: Array, window, causal: bool) -> Array:
    """Pallas flash path for full/prefill self- and cross-attention.

    q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd). GQA via kv-head repeat (a view; the
    kernel re-reads k/v blocks per q block anyway). window may be traced.
    """
    from repro.kernels.flash_attention import flash_attention
    from repro.dist import context as dctx
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, k.shape[1], k.shape[-1])
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, v.shape[1], v.shape[-1])
    w = window if window is not None else 0
    bq, bk, interp = _flash_schedule(qt.dtype, b * h, sq, kt.shape[1], hd)

    mesh = dctx.get_mesh()
    if mesh is None:
        out = flash_attention(qt, kt, vt, w, causal, interp, bq, bk)
        dv = out.shape[-1]   # MLA: value dim differs from q/k head dim
        return out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)
    # shard_map: flash is embarrassingly parallel over (batch, head); each
    # device runs the kernel on its local rows with ZERO collectives (without
    # this, the SPMD partitioner gathers q/k/v around the kernel — §Perf
    # starcoder2 iter-1 found 88TB of wire traffic).
    from jax.sharding import PartitionSpec as P
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def divides(n, axes):
        return n % int(np.prod([axis_size[a] for a in axes] or [1])) == 0

    bax = (batch_axes or None) if divides(b, batch_axes) else None
    w = jnp.asarray(w, jnp.int32)
    if "model" in axis_size and divides(h, ("model",)):
        # heads over "model", batch over the batch axes: the layout the
        # tensor-parallel projections already give q/k/v, so nothing moves
        # and the rest of the layer keeps its batch whole on every device
        # (sharding the fused B*H dim instead splits the batch, and the
        # partitioner then all-gathers every weight to follow it).
        sp = P(bax, "model", None, None)

        def local(q_, k_, v_, w_):
            lb, lh = q_.shape[:2]
            o = flash_attention(*(x.reshape(lb * lh, *x.shape[2:])
                                  for x in (q_, k_, v_)),
                                w_, causal, interp, bq, bk)
            return o.reshape(lb, lh, *o.shape[1:])

        out = jax.shard_map(local, mesh=mesh, in_specs=(sp, sp, sp, P()),
                            out_specs=sp, check_vma=False)(
            *(x.reshape(b, h, *x.shape[1:]) for x in (qt, kt, vt)), w)
        return out.transpose(0, 2, 1, 3)
    # heads do not divide the model axis: shard the fused (B*H) dim
    bh = b * h
    ladder = [batch_axes + (("model",) if "model" in axis_size else ()),
              batch_axes, ()]
    spec_axes = next(axes for axes in ladder if divides(bh, axes))
    sp = P(spec_axes if spec_axes else None, None, None)
    out = jax.shard_map(
        lambda q_, k_, v_, w_: flash_attention(q_, k_, v_, w_, causal,
                                               interp, bq, bk),
        mesh=mesh, in_specs=(sp, sp, sp, P()), out_specs=sp,
        check_vma=False,
    )(qt, kt, vt, w)
    dv = out.shape[-1]
    return out.reshape(b, h, sq, dv).transpose(0, 2, 1, 3)


def _sdpa(q: Array, k: Array, v: Array, keep: Optional[Array]) -> Array:
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd) -> (B,Sq,H,hd). GQA via head groups."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    group = h // kv
    q = q.reshape(b, sq, kv, group, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                        preferred_element_type=jnp.float32) / (hd ** 0.5)
    if keep is not None:
        scores = jnp.where(keep[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, hd)


def gqa_apply(p: dict, x: Array, *, cfg: ModelConfig, positions: Array,
              window=0, rope_theta=None, causal: bool = True,
              cache: Optional[dict] = None, cache_pos: Optional[Array] = None,
              cache_write_mask: Optional[Array] = None,
              prefill: bool = False, page_table: Optional[Array] = None,
              paged_impl: str = "gather") -> Tuple[Array, Optional[dict]]:
    """Full/prefill when cache is None; single-step decode when cache given.

    cache = {"k": (B, S_max, KV, hd), "v": ...}; cache_pos: scalar int32 —
    the number of tokens already in the cache (q is written at that offset).
    cache_write_mask: optional (B,) bool — rows with False keep their cached
    K/V (bucketed prefill into a shared slot cache).

    When ``page_table`` (B, max_pages) is given the cache leaves are page
    POOLS (P, ps, KV, hd) shared across sequences; k/v rows scatter through
    the table and attention runs either over the gathered contiguous view
    (``paged_impl="gather"`` — bit-identical to the contiguous decode branch)
    or the in-kernel-gather Pallas path (``paged_impl="flash"``). The paged
    branch serves both decode and chunked prefill (chunk rows attend the
    full gathered cache, so chunk boundaries never change the math).
    """
    b, s, d = x.shape
    hd = cfg.hd
    theta = cfg.rope_theta if rope_theta is None else rope_theta
    q = L.dense(x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = L.dense(x, p["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
    v = L.dense(x, p["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
    q = L.apply_rope(q, positions, theta)
    k = L.apply_rope(k, positions, theta)

    if cache is None:
        if cfg.attention_impl == "flash":
            out = _flash_sdpa(q, k, v, window, causal)
        else:
            keep = _mask(positions if positions.ndim == 2 else positions[None, :],
                         positions if positions.ndim == 2 else positions[None, :],
                         window, causal)
            if keep.ndim == 2:
                keep = keep[None]
            out = _sdpa(q, k, v, keep)
        new_cache = None
    elif page_table is not None:
        k_pool = _paged_write(cache["k"], k, page_table, cache_pos,
                              cache_write_mask)
        v_pool = _paged_write(cache["v"], v, page_table, cache_pos,
                              cache_write_mask)
        pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32).reshape(-1),
                               (b,))
        if paged_impl == "flash":
            from repro.core.gemm import current_config
            from repro.kernels.flash_attention import flash_attention_paged
            out = flash_attention_paged(
                q.transpose(0, 2, 1, 3), k_pool, v_pool, page_table,
                pos + s, pos, window if window is not None else 0,
                causal=causal, interpret=current_config().interpret)
            out = out.transpose(0, 2, 1, 3)
        else:
            kg = _paged_view(k_pool, page_table)
            vg = _paged_view(v_pool, page_table)
            s_max = kg.shape[1]
            k_pos = jnp.arange(s_max, dtype=jnp.int32)
            valid = k_pos[None, :] < _cache_end(pos, s)
            q_pos = positions if positions.ndim == 2 else positions[None, :]
            keep = _mask(q_pos, k_pos[None, :], window, causal) \
                & valid[:, None, :]
            out = _sdpa(q, kg, vg, keep)
        new_cache = {"k": k_pool, "v": v_pool}
    elif prefill and cfg.attention_impl == "flash":
        # prefill into EMPTY cache rows: attention over the prompt == flash
        # self-attention; k/v written at offset 0 (32k cells never touch an
        # (S,S) score tensor this way — §Perf)
        k_cache = _cache_write(cache["k"], k, cache_pos, cache_write_mask)
        v_cache = _cache_write(cache["v"], v, cache_pos, cache_write_mask)
        out = _flash_sdpa(q, k, v, window, causal)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        # decode: write this step's k/v at cache_pos (per-slot rows when
        # cache_pos is a (B,) vector), attend over the cache
        k_cache = _cache_write(cache["k"], k, cache_pos, cache_write_mask)
        v_cache = _cache_write(cache["v"], v, cache_pos, cache_write_mask)
        s_max = k_cache.shape[1]
        k_pos = jnp.arange(s_max, dtype=jnp.int32)
        valid = k_pos[None, :] < _cache_end(cache_pos, s)
        q_pos = positions if positions.ndim == 2 else positions[None, :]
        keep = _mask(q_pos, k_pos[None, :], window, causal) & valid[:, None, :]
        out = _sdpa(q, k_cache, v_cache, keep)
        new_cache = {"k": k_cache, "v": v_cache}
    return L.dense(out.reshape(b, s, cfg.n_heads * hd), p["wo"]), new_cache


# --- MLA (DeepSeek-V2) ------------------------------------------------------

def mla_init(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "wq": L.dense_init(k1, d, h * (m.nope_head_dim + m.rope_head_dim), dtype),
        "w_dkv": L.dense_init(k2, d, m.kv_lora_rank, dtype),    # compress
        "w_kr": L.dense_init(k3, d, m.rope_head_dim, dtype),    # shared rope key
        "w_ukv": L.dense_init(k4, m.kv_lora_rank,
                              h * (m.nope_head_dim + m.v_head_dim), dtype),
        "kv_norm": L.rmsnorm_init(m.kv_lora_rank, dtype),
        "wo": L.dense_init(k5, h * m.v_head_dim, d, dtype),
    }


def _mla_kv(p, c_kv: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    m = cfg.mla
    b, s, _ = c_kv.shape
    kv = L.dense(c_kv, p["w_ukv"]).reshape(b, s, cfg.n_heads,
                                           m.nope_head_dim + m.v_head_dim)
    return kv[..., :m.nope_head_dim], kv[..., m.nope_head_dim:]


def mla_apply(p: dict, x: Array, *, cfg: ModelConfig, positions: Array,
              window=0, cache: Optional[dict] = None,
              cache_pos: Optional[Array] = None,
              cache_write_mask: Optional[Array] = None,
              prefill: bool = False, page_table: Optional[Array] = None,
              paged_impl: str = "gather") -> Tuple[Array, Optional[dict]]:
    """MLA: the KV cache stores only (c_kv, k_rope) — rank-512+64 per token.

    cache = {"c_kv": (B, S_max, r), "k_rope": (B, S_max, rope_hd)};
    cache_write_mask as in :func:`gqa_apply`. With ``page_table`` set the
    leaves are pools (P, ps, r) / (P, ps, rope_hd) and the absorbed decode
    runs over the gathered view (or, for ``paged_impl="flash"``, the paged
    kernel with k = concat(c, rope), v = c and the pre-absorption scale —
    the flashinfer paged-MLA layout).
    """
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    q = L.dense(x, p["wq"]).reshape(b, s, h, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = q[..., :m.nope_head_dim], q[..., m.nope_head_dim:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    c_kv = L.rmsnorm(L.dense(x, p["w_dkv"]), p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(L.dense(x, p["w_kr"])[:, :, None, :], positions,
                          cfg.rope_theta)  # (B,S,1,rope_hd)

    if page_table is None and (cache is None
                               or (prefill and cfg.attention_impl == "flash")):
        k_nope, v = _mla_kv(p, c_kv, cfg)
        kr = k_rope
        kv_positions = positions if positions.ndim == 2 else positions[None, :]
        q_positions = kv_positions
        valid = None
        new_cache = None
        if cache is not None:   # prefill: write compressed cache, flash attn
            new_cache = {
                "c_kv": _cache_write(cache["c_kv"], c_kv, cache_pos,
                                     cache_write_mask),
                "k_rope": _cache_write(cache["k_rope"], k_rope[:, :, 0, :],
                                       cache_pos, cache_write_mask),
            }
        if cfg.attention_impl == "flash":
            # PERF (§Perf deepseek iter-1): flash for MLA — concat nope+rope
            # into q'/k' (d=192) with dv=128 values; no (S,S) scores in HBM.
            q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(kr, (*k_nope.shape[:3], m.rope_head_dim))],
                axis=-1)
            out = _flash_sdpa(q_full, k_full, v, 0, True)
            out = out.reshape(b, s, h * m.v_head_dim)
            return L.dense(out, p["wo"]), new_cache
    else:
        # PERF (§Perf beyond-paper, deepseek decode): ABSORBED MLA decode.
        # Instead of decompressing k/v for the whole cache per token
        # (S*H*(nope+v)*r flops + a (B,S,H,256) transient -> useful-flops
        # ratio 0.00 in the baseline roofline), absorb W_uk into the query
        # and W_uv into the context: attention runs entirely in the rank-r
        # latent space against the compressed cache.
        w_ukv = p["w_ukv"]["w"].reshape(m.kv_lora_rank, h,
                                        m.nope_head_dim + m.v_head_dim)
        w_uk = w_ukv[..., :m.nope_head_dim]            # (r, H, nope)
        w_uv = w_ukv[..., m.nope_head_dim:]            # (r, H, v)
        q_eff = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)   # absorbed query
        if page_table is not None:
            c_pool = _paged_write(cache["c_kv"], c_kv, page_table, cache_pos,
                                  cache_write_mask)
            r_pool = _paged_write(cache["k_rope"], k_rope[:, :, 0, :],
                                  page_table, cache_pos, cache_write_mask)
            new_cache = {"c_kv": c_pool, "k_rope": r_pool}
            pos = jnp.broadcast_to(
                jnp.asarray(cache_pos, jnp.int32).reshape(-1), (b,))
            if paged_impl == "flash":
                from repro.core.gemm import current_config
                from repro.kernels.flash_attention import flash_attention_paged
                q_cat = jnp.concatenate([q_eff, q_rope], axis=-1)
                k_cat = jnp.concatenate([c_pool, r_pool], -1)[:, :, None, :]
                ctx = flash_attention_paged(
                    q_cat.transpose(0, 2, 1, 3), k_cat,
                    c_pool[:, :, None, :], page_table, pos + s, pos, 0,
                    scale=1.0 / ((m.nope_head_dim + m.rope_head_dim) ** 0.5),
                    interpret=current_config().interpret)
                ctx = ctx.transpose(0, 2, 1, 3)        # (B, s, H, r)
                out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)
                out = out.reshape(b, s, h * m.v_head_dim)
                return L.dense(out, p["wo"]), new_cache
            c_cache = _paged_view(c_pool, page_table)
            r_cache = _paged_view(r_pool, page_table)
            cache_pos = pos
        else:
            c_cache = _cache_write(cache["c_kv"], c_kv, cache_pos,
                                   cache_write_mask)
            r_cache = _cache_write(cache["k_rope"], k_rope[:, :, 0, :],
                                   cache_pos, cache_write_mask)
            new_cache = {"c_kv": c_cache, "k_rope": r_cache}
        s_max = c_cache.shape[1]
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_eff.astype(jnp.float32),
                             c_cache.astype(jnp.float32))
                  + jnp.einsum("bqhd,bsd->bhqs", q_rope.astype(jnp.float32),
                               r_cache.astype(jnp.float32)))
        scores = scores / ((m.nope_head_dim + m.rope_head_dim) ** 0.5)
        kv_positions = jnp.broadcast_to(
            jnp.arange(s_max, dtype=jnp.int32)[None], (b, s_max))
        q_positions = positions if positions.ndim == 2 else positions[None, :]
        keep = _mask(q_positions, kv_positions, window, True) \
            & (kv_positions < _cache_end(cache_pos, s))[:, None, :]
        scores = jnp.where(keep[:, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum("bhqs,bsr->bqhr", probs.astype(c_cache.dtype), c_cache)
        out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)   # absorbed values
        out = out.reshape(b, s, h * m.v_head_dim)
        return L.dense(out, p["wo"]), new_cache

    scores = (jnp.einsum("bqhd,bshd->bhqs", q_nope, k_nope,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bqhd,bsxd->bhqs", q_rope,
                           jnp.broadcast_to(kr, (*kr.shape[:2], 1, kr.shape[-1])),
                           preferred_element_type=jnp.float32))
    scores = scores / ((m.nope_head_dim + m.rope_head_dim) ** 0.5)
    keep = _mask(q_positions, kv_positions, window, True)
    if valid is not None:
        keep = keep & valid[:, None, :]
    scores = jnp.where(keep[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", probs.astype(v.dtype), v)
    out = out.reshape(b, s, h * m.v_head_dim)
    return L.dense(out, p["wo"]), new_cache


# --- Cross-attention (whisper decoder) ---------------------------------------

def cross_init(key, cfg: ModelConfig, dtype) -> dict:
    d, hd = cfg.d_model, cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(k1, d, cfg.n_heads * hd, dtype),
        "wk": L.dense_init(k2, d, cfg.n_kv_heads * hd, dtype),
        "wv": L.dense_init(k3, d, cfg.n_kv_heads * hd, dtype),
        "wo": L.dense_init(k4, cfg.n_heads * hd, d, dtype),
    }


def cross_apply(p: dict, x: Array, enc: Array, cfg: ModelConfig) -> Array:
    """x: (B,S,d) queries over encoder states enc: (B,T,d). No mask."""
    b, s, d = x.shape
    hd = cfg.hd
    q = L.dense(x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = L.dense(enc, p["wk"]).reshape(b, enc.shape[1], cfg.n_kv_heads, hd)
    v = L.dense(enc, p["wv"]).reshape(b, enc.shape[1], cfg.n_kv_heads, hd)
    out = _sdpa(q, k, v, None)
    return L.dense(out.reshape(b, s, cfg.n_heads * hd), p["wo"])
