"""FIP (Winograd 1968) GEMM as a Pallas TPU kernel — Fig. 1b adapted to TPU.

TPU adaptation: the FIP PE trades half the multipliers for pre-adders. On TPU
there is no MXU mapping for the (i,j)-coupled pre-add, so the kernel performs
the halved-multiplication algebra on the VPU with explicit VMEM blocking: per
(bm, bk, bn) tile it forms the two pre-add tensors, multiplies elementwise,
reduces over the pair axis, and accumulates cross − α_blk − β_blk into the
output block. The α row of the paper's MXU (Fig. 3) corresponds to the
in-kernel α_blk computation; β may be pre-folded into the bias by the caller
(Eq. 15), in which case the kernel skips the β term.

Layout: Mosaic cannot take strided lane slices, so the pair operands arrive
DEINTERLEAVED — A as its odd and even columns (a_{i,2k-1} / a_{i,2k}), B as
its odd and even rows — and every block reads them contiguously. A k block of
``bk`` covers ``bk/2`` pairs. The pair axis is reduced ``PAIR_CHUNK`` pairs at
a time so the (bm, chunk, bn) intermediates stay small in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import resolve_interpret, tpu_compiler_params

Array = jax.Array

# Pairs reduced per step of the in-tile loop: (bm, PAIR_CHUNK, bn) f32
# intermediates are 512 KiB at bm = bn = 128.
PAIR_CHUNK = 8


def deinterleave(x: Array, axis: int) -> tuple:
    """(odd, even) halves of ``x`` along ``axis`` in the paper's 1-based
    naming: odd = positions 0, 2, 4, ... and even = 1, 3, 5, ..."""
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(0, None, 2)
    odd = x[tuple(idx)]
    idx[axis] = slice(1, None, 2)
    return odd, x[tuple(idx)]


def pad_to(x: Array, shape) -> Array:
    """Zero-pad ``x`` up to ``shape``. Zero pairs pre-add to zero and zero
    rows/columns add nothing to cross, alpha or beta, so padding is exact."""
    if tuple(x.shape) == tuple(shape):
        return x
    return jnp.pad(x, [(0, s - d) for d, s in zip(x.shape, shape)])


def cross_term(a_first, a_second, b_first, b_second):
    """sum_k (a_first[:, k] + b_first[k, :]) * (a_second[:, k] + b_second[k, :])
    over one tile's pairs, PAIR_CHUNK pairs at a time. a_*: (bm, P),
    b_*: (P, bn) -> (bm, bn)."""
    bm, pairs = a_first.shape
    out = jnp.zeros((bm, b_first.shape[1]), a_first.dtype)
    for c0 in range(0, pairs, PAIR_CHUNK):
        c1 = min(c0 + PAIR_CHUNK, pairs)
        # slice, then add the axis: a combined index lowers to a gather,
        # which Mosaic rejects
        t1 = a_first[:, c0:c1][:, :, None] + b_first[c0:c1][None]
        t2 = a_second[:, c0:c1][:, :, None] + b_second[c0:c1][None]
        out = out + jnp.sum(t1 * t2, axis=1)
    return out


def fip_tile(a_odd, a_evn, b_odd, b_evn, *, fold_beta: bool):
    """Eq. (2) on one tile of deinterleaved operands: pre-add, multiply,
    reduce over the pair axis, subtract the alpha (and beta unless folded)
    rows. SHARED between this GEMM kernel and the fused implicit-im2col conv
    kernels (kernels/conv_gemm.py) — one algebra, two A-tile sources, so the
    fused conv is bit-identical to the materialized GEMM by construction."""
    # Eq. (2) cross term: (a_{i,2k-1} + b_{2k,j}) (a_{i,2k} + b_{2k-1,j})
    part = cross_term(a_odd, a_evn, b_evn, b_odd)
    part = part - jnp.sum(a_odd * a_evn, axis=1, keepdims=True)  # Eq. (3)
    if not fold_beta:
        part = part - jnp.sum(b_odd * b_evn, axis=0, keepdims=True)  # Eq. (4)
    return part


def _kernel(ao_ref, ae_ref, bo_ref, be_ref, o_ref, *, acc_dtype, fold_beta):
    kk = pl.program_id(2)
    part = fip_tile(ao_ref[...].astype(acc_dtype), ae_ref[...].astype(acc_dtype),
                    bo_ref[...].astype(acc_dtype), be_ref[...].astype(acc_dtype),
                    fold_beta=fold_beta)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = part

    @pl.when(kk != 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "fold_beta"))
def fip_gemm(a: Array, b: Array, *, bm: int = 128, bn: int = 128,
             bk: int = 256, interpret=None, fold_beta: bool = False) -> Array:
    """a: (M, K), b: (K, N) -> (M, N) via Eq. (2). bk must be even (a block
    holds bk/2 pairs); shapes not divisible by the blocks are zero-padded and
    the result sliced (zero pairs pre-add to zero, so cross/alpha/beta are
    unchanged — exact). With ``fold_beta=True`` the caller is expected to add
    ``fold_beta_into_bias(b)`` (Eq. 15) afterwards — the hardware's free beta
    handling. ``interpret=None`` auto-detects the backend (compat.py)."""
    interpret = resolve_interpret(interpret)
    if bk % 2:
        raise ValueError(f"bk={bk} must be even for the FIP pair algebra")
    m0, k0 = a.shape
    k2, n0 = b.shape
    if k0 != k2:
        raise ValueError(f"contraction mismatch {k0} vs {k2}")
    bkh = bk // 2
    m, n = -(-m0 // bm) * bm, -(-n0 // bn) * bn
    kh = -(-k0 // bk) * bkh
    ao, ae = (pad_to(x, (m, kh)) for x in deinterleave(a, 1))
    bo, be = (pad_to(x, (kh, n)) for x in deinterleave(b, 0))
    acc_dtype = (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                 else jnp.float32)
    a_spec = pl.BlockSpec((bm, bkh), lambda i, j, kk: (i, kk))
    b_spec = pl.BlockSpec((bkh, bn), lambda i, j, kk: (kk, j))
    out = pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype, fold_beta=fold_beta),
        grid=(m // bm, n // bn, kh // bkh),
        in_specs=[a_spec, a_spec, b_spec, b_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ao, ae, bo, be)
    return out[:m0, :n0]
