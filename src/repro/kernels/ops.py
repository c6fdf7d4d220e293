"""Public jit'd wrappers over the Pallas GEMM kernels.

Handles: leading batch dims, dtype policy (int8→int32 accumulation,
bf16→f32), default block selection (:func:`choose_blocks`: TPU-legal and
VMEM-bounded), and output casting. Padding to block multiples lives in the
kernels themselves (``baseline_gemm.pad_to_blocks``, ``fip_gemm.pad_to`` —
zero rows/cols are exact for the baseline products and the FIP/FFIP
cross/α/β algebra), so any caller — this wrapper, the repro.tune measurement
harness, or a direct kernel user — gets the same pad-run-slice fallback.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.baseline_gemm import baseline_gemm
from repro.kernels.compat import resolve_interpret
from repro.kernels.fip_gemm import PAIR_CHUNK, fip_gemm
from repro.kernels.ffip_gemm import ffip_gemm
from repro.obs import profile as _obs_profile

Array = jax.Array

# Scoped-VMEM budget a tile may plan for (bytes). A v5e core has 16 MiB of
# scoped VMEM by default; the rest is headroom for Mosaic's own buffers.
_VMEM_BUDGET = 6 * 1024 * 1024
# TPU block rule: a block's minor dimension is a multiple of LANE and its
# second-minor a multiple of SUBLANE, unless the block spans the whole
# (padded) dimension — which is what a block >= the problem dimension gives,
# since the kernels pad every operand to block multiples.
LANE = 128
SUBLANE = 8


def tile_vmem_bytes(bm: int, bn: int, bk: int, algo: str,
                    itemsize: int = 4) -> int:
    """VMEM one grid step plans for: double-buffered operand blocks, the
    double-buffered f32/int32 output block, and for the FIP family the
    (bm, PAIR_CHUNK, bn) pre-add/product intermediates of the pair loop."""
    total = 2 * (bm * bk + bk * bn) * itemsize + 2 * bm * bn * 4
    if algo != "baseline":
        total += 3 * bm * PAIR_CHUNK * bn * 4
    return total


def choose_blocks(m: int, n: int, k: int, algo: str) -> Tuple[int, int, int]:
    """Static default (bm, bn, bk): TPU-legal for any problem shape (see
    LANE/SUBLANE) and within the VMEM budget. For the FIP family bk/2 pairs
    make up one lane block of each deinterleaved half."""
    bm = min(128, _round_up_pow2(m))
    bn = min(LANE, _round_up_pow2(n))
    if algo == "baseline":
        bk = min(4 * LANE, _round_up_pow2(k))
    else:
        bk = 2 * min(LANE, _round_up_pow2(-(-k // 2)))
    return bm, bn, bk


def _round_up_pow2(x: int) -> int:
    p = SUBLANE
    while p < x:
        p *= 2
    return p


def matmul(a: Array, b: Array, *, algo: str = "ffip", interpret=None,
           bm: int = 0, bn: int = 0, bk: int = 0) -> Array:
    """C = A @ B via the Pallas kernels. a: (..., M, K), b: (K, N).

    Returns the result cast back to the promoted input dtype for floats and
    int32 for integer inputs (hardware-accumulator semantics).
    ``interpret=None`` auto-detects the backend (kernels/compat.py); pass
    ``bm``/``bn``/``bk`` (e.g. from a ``repro.tune`` schedule) to override the
    static default blocks.

    Thin python wrapper over the jitted core so ``repro.obs.profile`` sees
    every dispatch (eager call = dispatch; tracer operands = compile-side).
    """
    _obs_profile.on_gemm(a, b, algo)
    return _matmul_jit(a, b, algo=algo, interpret=interpret,
                       bm=bm, bn=bn, bk=bk)


@functools.partial(jax.jit, static_argnames=("algo", "interpret", "bm", "bn", "bk"))
def _matmul_jit(a: Array, b: Array, *, algo: str = "ffip", interpret=None,
                bm: int = 0, bn: int = 0, bk: int = 0) -> Array:
    interpret = resolve_interpret(interpret)
    *batch, m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {k} vs {k2}")
    a2 = a.reshape(-1, k) if batch else a
    mm = a2.shape[0]

    if not (bm and bn and bk):
        bm, bn, bk = choose_blocks(mm, n, k, algo)

    # non-divisible shapes are padded/sliced inside the kernels (exactly)
    if algo == "baseline":
        out = baseline_gemm(a2, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
    elif algo == "fip":
        out = fip_gemm(a2, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
    elif algo == "ffip":
        out = ffip_gemm(a2, b, bm=bm, bn=bn, bk=bk, interpret=interpret)
    else:
        raise ValueError(algo)

    if batch:
        out = out.reshape(*batch, m, n)
    if jnp.issubdtype(a.dtype, jnp.integer):
        return out  # int32 accumulator, caller rescales
    return out.astype(jnp.result_type(a.dtype, b.dtype))
