"""Baseline systolic-array-style blocked GEMM as a Pallas TPU kernel.

The comparison baseline (Fig. 1a PEs): a straightforward MXU-mapped blocked
matmul with explicit BlockSpec VMEM tiling. Grid (M/bm, N/bn, K/bk), K
innermost for in-VMEM accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.compat import resolve_interpret, tpu_compiler_params

Array = jax.Array


def pad_to_blocks(a: Array, b: Array, bm: int, bn: int, bk: int):
    """Zero-pad (M, K) x (K, N) operands to block multiples. Shared by every
    GEMM kernel's pad-run-slice fallback: zero rows/columns contribute zero to
    baseline products AND to the FIP-family cross/alpha/beta terms (pairs of
    zeros pre-add to zero), so padding is exact — the caller slices the
    (m, n) corner back out. Keeps the tuner free to consider any legal block
    on any shape, and odd model dims out of the assert graveyard."""
    m, k = a.shape
    n = b.shape[1]
    mp, kp, np_ = -(-m // bm) * bm, -(-k // bk) * bk, -(-n // bn) * bn
    if (mp, kp) != (m, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    return a, b


def _kernel(a_ref, b_ref, o_ref, *, acc_dtype):
    k = pl.program_id(2)
    # Operands go to the MXU in their own dtype (int8 x int8 -> int32, bf16 x
    # bf16 -> f32); Mosaic refuses an int32 x int32 dot.
    part = jax.lax.dot_general(a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
                               preferred_element_type=acc_dtype)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = part

    @pl.when(k != 0)
    def _acc():
        o_ref[...] += part


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def baseline_gemm(a: Array, b: Array, *, bm: int = 128, bn: int = 128,
                  bk: int = 128, interpret=None) -> Array:
    """a: (M, K), b: (K, N) -> (M, N) in the accumulation dtype.

    Shapes not divisible by the blocks are zero-padded and the result sliced
    (exact). ``interpret=None`` auto-detects: compiled on TPU, interpret mode
    elsewhere (kernels/compat.py); pass a bool to override.
    """
    interpret = resolve_interpret(interpret)
    m0, k0 = a.shape
    k2, n0 = b.shape
    if k0 != k2:
        raise ValueError(f"contraction mismatch {k0} vs {k2}")
    dtype = jnp.result_type(a.dtype, b.dtype)
    a, b = pad_to_blocks(a.astype(dtype), b.astype(dtype), bm, bn, bk)
    m, k = a.shape
    n = b.shape[1]
    acc_dtype = (jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                 else jnp.float32)
    grid = (m // bm, n // bn, k // bk)
    out = pl.pallas_call(
        functools.partial(_kernel, acc_dtype=acc_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out[:m0, :n0]
