"""FFIP GEMM as a Pallas TPU kernel — Fig. 1c / Fig. 3 adapted to TPU.

Faithful free-pipeline dataflow: the kernel consumes the weight *deltas*
y (Eq. 9) rather than B, and reconstructs the g-term offsets by accumulating
y along the output-column direction — exactly what the FFIP PE chain does,
where each g register adds one y as the value hops to the next column's PE.

Mapping to a blocked kernel: each ``bn``-column block of the output is its
own FFIP array whose first column is fed b itself (as the paper's array is
fed y_{i,1} = b_{i,1}): :func:`block_deltas` re-encodes the Eq. 9 deltas so
every block starts from b, and the kernel rebuilds the block's B tile with a
log-step roll-and-add prefix sum along the columns. Blocks need no state from
one another, so the grid is (M/bm, N/bn, K/bk) with K innermost and the
output block accumulates in place, as in fip_gemm. B is never materialised in
HBM — only y travels (the paper's §4.4 notes y can be precomputed and stored
at 1 extra bit). Operands use fip_gemm's deinterleaved pair layout.

The α row is computed in-kernel (the paper's extra MAC row, Fig. 3); β is
reconstructed from the rebuilt tile (or pre-folded into bias, Eq. 15).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compat
from repro.kernels.compat import resolve_interpret, tpu_compiler_params
from repro.kernels.fip_gemm import cross_term, deinterleave, pad_to

from repro.core import fip

Array = jax.Array

# Per-weight y-delta cache (§4.4: y is precomputed offline and stored in
# place of B), shared with conv_gemm through compat.derived and seeded by
# repro.prepare on artifact warm start (tag "y").
Y_TAG = "y"


def _y_for(b: Array) -> Array:
    return compat.derived.get(Y_TAG, b, fip.make_y)


def lane_prefix_sum(x: Array) -> Array:
    """Inclusive prefix sum along the lane (last) axis of a 2-D tile in
    log2(width) roll-and-add steps (Hillis-Steele): Mosaic lowers
    ``pltpu.roll`` but not ``cumsum``. Exact for integers."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    shift = 1
    while shift < x.shape[1]:
        x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1),
                          jnp.zeros_like(x))
        shift *= 2
    return x


def block_deltas(y: Array, bn: int) -> Array:
    """Re-encode Eq. 9 deltas ``y`` (..., K, N), N a multiple of ``bn``, so
    the first column of every ``bn``-column block holds b itself: add to it
    the sum of all deltas before the block. Exact for integers."""
    *lead, k, n = y.shape
    y3 = y.reshape(*lead, k, n // bn, bn)
    sums = jnp.sum(y3, axis=-1)
    before = jnp.cumsum(sums, axis=-1) - sums       # b just before each block
    return y3.at[..., 0].add(before).reshape(y.shape)


def ffip_tile(a_odd, a_evn, y_odd, y_evn, *, fold_beta: bool):
    """Eqs. (7)-(9) on one tile of deinterleaved operands whose y block
    starts from b (:func:`block_deltas`): rebuild the weight tile by the
    column prefix sum, then the pair product-sum minus alpha (and beta
    unless folded). SHARED between this GEMM kernel and the fused
    implicit-im2col conv kernels (kernels/conv_gemm.py) — one algebra, two
    A-tile sources, so fused conv == materialized GEMM bit-for-bit."""
    # Free-pipeline reconstruction: b_{k,j} = b_{k,j-1} + y_{k,j} (Eq. 8c/9).
    b_odd = lane_prefix_sum(y_odd)                      # (bk/2, bn)
    b_evn = lane_prefix_sum(y_evn)

    # g terms (Eqs. 8a/8b): pair-swapped A plus the reconstructed offsets,
    # g_{i,2k-1} = a_{i,2k} + b_{2k-1,j} and g_{i,2k} = a_{i,2k-1} + b_{2k,j}.
    part = cross_term(a_evn, a_odd, b_odd, b_evn)       # Eq. (7) product-sum
    part = part - jnp.sum(a_odd * a_evn, axis=1, keepdims=True)  # alpha row
    if not fold_beta:
        part = part - jnp.sum(b_odd * b_evn, axis=0, keepdims=True)
    return part


def _kernel(ao_ref, ae_ref, yo_ref, ye_ref, o_ref, *, acc_dtype, fold_beta):
    kk = pl.program_id(2)
    part = ffip_tile(ao_ref[...].astype(acc_dtype),
                     ae_ref[...].astype(acc_dtype),
                     yo_ref[...].astype(acc_dtype),
                     ye_ref[...].astype(acc_dtype), fold_beta=fold_beta)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = part

    @pl.when(kk != 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "fold_beta"))
def ffip_gemm_y(a: Array, y: Array, *, bm: int = 128, bn: int = 128,
                bk: int = 256, interpret=None,
                fold_beta: bool = False) -> Array:
    """FFIP GEMM from precomputed y deltas. a: (M, K), y: (K, N) -> (M, N).

    Non-divisible shapes zero-pad and slice (exact for the returned corner:
    zero y rows reconstruct zero b rows against zero a columns, and padded N
    columns only repeat the last real column of b). bk must be even (bk/2
    pairs per block); ``interpret=None`` = backend auto."""
    interpret = resolve_interpret(interpret)
    if bk % 2:
        raise ValueError(f"bk={bk} must be even for the FIP pair algebra")
    m0, k0 = a.shape
    k2, n0 = y.shape
    if k0 != k2:
        raise ValueError(f"contraction mismatch {k0} vs {k2}")
    bkh = bk // 2
    m, n = -(-m0 // bm) * bm, -(-n0 // bn) * bn
    kh = -(-k0 // bk) * bkh
    ao, ae = (pad_to(x, (m, kh)) for x in deinterleave(a, 1))
    yb = block_deltas(pad_to(y, (k0, n)), bn)
    yo, ye = (pad_to(x, (kh, n)) for x in deinterleave(yb, 0))
    acc_dtype = (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                 else jnp.float32)
    a_spec = pl.BlockSpec((bm, bkh), lambda i, j, kk: (i, kk))
    y_spec = pl.BlockSpec((bkh, bn), lambda i, j, kk: (kk, j))
    out = pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype, fold_beta=fold_beta),
        grid=(m // bm, n // bn, kh // bkh),
        in_specs=[a_spec, a_spec, y_spec, y_spec],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ao, ae, yo, ye)
    return out[:m0, :n0]


def ffip_gemm(a: Array, b: Array, *, y: Array = None, **kw) -> Array:
    """Convenience: derive y from B (offline in deployment) then run FFIP.

    y is kept in the accumulation dtype (int32 / f32): the paper stores y with
    1 extra bit (§4.4) so the delta encoding is lossless; for bf16 weights the
    f32 deltas play that role (bf16 deltas would make the column prefix-sum
    reconstruction lossy).

    The derivation is MEMOIZED per weight array (or pass a precomputed ``y``
    directly), matching the paper's deployment story: y is an offline
    transform of the trained weights, not per-invocation work.
    """
    if y is None:
        y = _y_for(b)  # make_y already promotes to the accumulation dtype
    return ffip_gemm_y(a, y, **kw)
