"""Flash attention (fwd + bwd) as Pallas TPU kernels.

Beyond-paper optimization (EXPERIMENTS.md §Perf): the naive attention path
materializes (B,H,S,S) scores in HBM — the dominant memory-roofline term for
every full/windowed-attention train & prefill cell. These kernels keep score
blocks in VMEM (classic FlashAttention-2 scheme, re-tiled for TPU: 128-aligned
blocks for the MXU, f32 running stats in VMEM scratch).

Supports causal masking and sliding windows (window=0 -> full causal);
GQA handled by the caller mapping kv-head = q-head // group.

HBM traffic: q, o read/written once; k/v re-read once per q-block — exactly
what launch/costs.py accounts for pallas_call eqns.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import resolve_interpret, tpu_compiler_params
from repro.obs import profile as _obs_profile

Array = jax.Array
NEG_INF = -1e30


def _fwd_kernel(w_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, bq, bk, seq_k, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (bq, d)
    k = k_ref[0]                                   # (bk, d); v may have dv != d
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    w = w_ref[0, 0]   # dynamic sliding window; <=0 means full attention
    mask = jnp.logical_and(mask, jnp.logical_or(w <= 0, q_pos - k_pos < w))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # Zero p where masked: for a fully-masked block m_new stays NEG_INF and
    # exp(s - m_new) = exp(0) = 1 per entry, which would pollute l/acc with
    # bk phantom counts (and only self-correct if a LATER block has a valid
    # entry). Paged/chunked-prefill masks hit that case directly.
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == pl.num_programs(2) - 1)
    def _fin():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def _flash_fwd(q, k, v, window, *, causal=True, bq=128, bk=128,
               interpret=None) -> Tuple[Array, Array]:
    """q: (BH, Sq, d), k/v: (BH, Sk, d), window: () int32 (traced OK, <=0 =
    full) -> (out (BH,Sq,d), lse (BH,Sq,1)). lse keeps a trailing unit dim:
    its (1, bq, 1) block spans the whole minor dim, which TPU accepts, where
    a (1, bq) block of a (BH, Sq) array would put bq on the lanes."""
    interpret = resolve_interpret(interpret)
    window = jnp.asarray(window, jnp.int32).reshape(1, 1)
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    dv = v.shape[-1]                     # MLA: value dim may differ from d_qk
    bq = min(bq, sq)
    bk = min(bk, sk)
    sq_pad = -(-sq // bq) * bq
    sk_pad = -(-sk // bk) * bk
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))
    grid = (bh, sq_pad // bq, sk_pad // bk)
    scale = 1.0 / (d ** 0.5)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk, seq_k=sk,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, sq_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(window, q, k, v)
    return out[:, :sq], lse[:, :sq]


def _bwd_kernel(w_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                dk_ref, dv_ref, *, scale, bq, bk, seq_k, causal):
    """One pass per (bh, kj, qi): accumulate dk/dv for this k block over q
    blocks (qi innermost), and contribute dq for each q block via accumulation.
    """
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    o = o_ref[0].astype(jnp.float32)
    lse = lse_ref[0]                                # (bq, 1)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    w = w_ref[0, 0]
    mask = jnp.logical_and(mask, jnp.logical_or(w <= 0, q_pos - k_pos < w))
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # (bq, bk)

    delta = jnp.sum(do * o, axis=1, keepdims=True)        # (bq, 1)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale                          # (bq, bk)

    @pl.when(qi == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    dv_ref[0] += jax.lax.dot_general(
        p.astype(jnp.float32), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dk_ref[0] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)
    # dq accumulated across k blocks: kj is the OUTER grid dim, so each
    # (qi) block is revisited once per kj -> accumulate into dq.
    dq_part = jax.lax.dot_general(ds, k.astype(jnp.float32),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(kj == 0)
    def _dq_init():
        dq_ref[0] = dq_part.astype(dq_ref.dtype)

    @pl.when(kj != 0)
    def _dq_acc():
        dq_ref[0] += dq_part.astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def _flash_bwd(q, k, v, o, lse, do, window, *, causal=True, bq=128, bk=128,
               interpret=None):
    interpret = resolve_interpret(interpret)
    window = jnp.asarray(window, jnp.int32).reshape(1, 1)
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    dv = v.shape[-1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    sq_pad = -(-sq // bq) * bq
    sk_pad = -(-sk // bk) * bk
    if sq_pad != sq:
        pad = ((0, 0), (0, sq_pad - sq), (0, 0))
        q = jnp.pad(q, pad)
        o = jnp.pad(o, pad)
        do = jnp.pad(do, pad)
        lse = jnp.pad(lse, ((0, 0), (0, sq_pad - sq), (0, 0)),
                      constant_values=1e30)
    if sk_pad != sk:
        pad = ((0, 0), (0, sk_pad - sk), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    # grid: kj outer / qi inner so dq blocks accumulate across consecutive steps
    grid = (bh, sk_pad // bk, sq_pad // bq)
    scale = 1.0 / (d ** 0.5)
    f32 = jnp.float32
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk, seq_k=sk,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, bq, dv), lambda b, j, i: (b, i, 0)),  # do
            pl.BlockSpec((1, bq, dv), lambda b, j, i: (b, i, 0)),  # o
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),   # lse
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),   # dq
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),   # dk
            pl.BlockSpec((1, bk, dv), lambda b, j, i: (b, j, 0)),  # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq_pad, d), f32),
            jax.ShapeDtypeStruct((bh, sk_pad, d), f32),
            jax.ShapeDtypeStruct((bh, sk_pad, dv), f32),
        ],
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(window, q, k, v, do, o, lse)
    return dq[:, :sq], dk[:, :sk], dv[:, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention(q: Array, k: Array, v: Array, window=0,
                    causal: bool = True, interpret=None,
                    bq: int = 128, bk: int = 128) -> Array:
    """q: (BH, Sq, d), k/v: (BH, Sk, d) -> (BH, Sq, d).

    ``window`` may be a TRACED int32 scalar (<=0 = full attention) — gemma3's
    per-layer local/global pattern rides through the layer scan this way.
    ``interpret=None`` auto-detects the backend (compat.py); ``bq``/``bk``
    are the q/k sequence block sizes — the attention layer resolves tuned
    values through ``repro.tune`` under ``GemmConfig(block="auto")``."""
    _obs_profile.on_flash(q, k, causal=causal)
    out, _ = _flash_fwd(q, k, v, window, causal=causal, interpret=interpret,
                        bq=bq, bk=bk)
    return out


def _fa_fwd(q, k, v, window, causal, interpret, bq, bk):
    out, lse = _flash_fwd(q, k, v, window, causal=causal, interpret=interpret,
                          bq=bq, bk=bk)
    return out, (q, k, v, out, lse, window)


def _fa_bwd(causal, interpret, bq, bk, res, do):
    import numpy as _np
    q, k, v, out, lse, window = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, window, causal=causal,
                            bq=bq, bk=bk, interpret=interpret)
    dw = _np.zeros((), jax.dtypes.float0)   # int operand: symbolic zero grad
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype), dw


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# -- paged decode attention ---------------------------------------------------

def _paged_fwd_kernel(pt_ref, len_ref, qs_ref, w_ref, q_ref, k_ref, v_ref,
                      o_ref, m_scr, l_scr, acc_scr, *, scale, sq, ps, causal):
    """One (b, h, page) step of the online softmax over paged k/v.

    k_ref/v_ref already hold the POOL page selected by the scalar-prefetch
    index map (page_table[b, j]); this body only has to mask by true length
    and fold the page into the running (m, l, acc) stats."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                # (sq, d)
    k = k_ref[0, :, 0, :]                          # (ps, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qs_ref[b] + jax.lax.broadcasted_iota(jnp.int32, (sq, ps), 0)
    k_pos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (sq, ps), 1)
    mask = k_pos < len_ref[b]
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    w = w_ref[0]
    mask = jnp.logical_and(mask, jnp.logical_or(w <= 0, q_pos - k_pos < w))
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                            # (sq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)   # (sq, ps)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, :, 0, :], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        # Rows with zero valid keys keep l == 0 -> output exactly 0 (not the
        # mean of garbage v rows; see the masked-p note in _fwd_kernel).
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "interpret"))
def flash_attention_paged(q: Array, k_pool: Array, v_pool: Array,
                          page_table: Array, lengths: Array, q_start: Array,
                          window=0, *, scale: Optional[float] = None,
                          causal: bool = True, interpret=None) -> Array:
    """Decode-side paged attention: k/v live in a page pool and are gathered
    through the page table INSIDE the kernel (scalar-prefetch index maps pick
    the pool page per grid step — no materialized contiguous copy).

    q:          (B, H, Sq, d)   — Sq is the decode chunk (1 for single-step)
    k_pool:     (P, ps, KV, d)  — KV kv-heads, q-head h uses kv-head h*KV//H
    v_pool:     (P, ps, KV, dv) — dv may differ from d (absorbed MLA)
    page_table: (B, max_pages) int32 pool page ids (unallocated entries may
                be anything in range; they are masked by ``lengths``)
    lengths:    (B,) int32 — number of valid cache rows (keys) per sequence
    q_start:    (B,) int32 — absolute position of q row 0
    window:     () int32 (traced OK; <=0 = full attention)
    scale:      score scale; default 1/sqrt(d) (absorbed MLA passes the
                1/sqrt(nope+rope) of the pre-absorption head dim)
    -> (B, H, Sq, dv). Query rows with zero valid keys return exactly 0.
    """
    interpret = resolve_interpret(interpret)
    b, h, sq, d = q.shape
    n_pages, ps, kv, _ = k_pool.shape
    dv = v_pool.shape[-1]
    max_pages = page_table.shape[1]
    group = max(h // kv, 1)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    pt = jnp.asarray(page_table, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32).reshape(b)
    qs = jnp.asarray(q_start, jnp.int32).reshape(b)
    w = jnp.asarray(window, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, h, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, sq, d),
                         lambda bi, hi, j, pt, ln, qs, w: (bi, hi, 0, 0)),
            pl.BlockSpec((1, ps, 1, d),
                         lambda bi, hi, j, pt, ln, qs, w:
                         (pt[bi, j], 0, hi // group, 0)),
            pl.BlockSpec((1, ps, 1, dv),
                         lambda bi, hi, j, pt, ln, qs, w:
                         (pt[bi, j], 0, hi // group, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, sq, dv), lambda bi, hi, j, pt, ln, qs, w: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, 1), jnp.float32),
            pltpu.VMEM((sq, dv), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_fwd_kernel, scale=scale, sq=sq, ps=ps,
                          causal=causal),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(pt, ln, qs, w, q, k_pool, v_pool)
