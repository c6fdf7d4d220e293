"""Quantization substrate + the paper's ML-specific (F)FIP optimizations (§3.3, §4.4).

Implements:
  * symmetric / asymmetric per-tensor & per-channel int8/int16 quantization
    (Jacob et al. scheme the paper builds on),
  * the "both signed or both unsigned" recommendation (§4.4) — the ``d``
    bit-growth parameter and range checks,
  * beta folding into the bias (Eqs. 15/16),
  * the zero-point adjuster (Eq. 20): for weights stored with a constant
    zero-point matrix R, A(B+R) = AB + AR, and AR_ij = r_j * rowsum(A)_i is
    computable with ONE multiplier per output — folded into the alpha path.

Everything integer is bit-exact: quantized FIP/FFIP GEMM == quantized
baseline GEMM, validated in tests/test_quant.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fip

Array = jax.Array

_INT_INFO = {
    jnp.int8.dtype: (-128, 127),
    jnp.uint8.dtype: (0, 255),
    jnp.int16.dtype: (-(2 ** 15), 2 ** 15 - 1),
    jnp.uint16.dtype: (0, 2 ** 16 - 1),
}

# Offline-prep work counter: every per-layer weight quantization (dense AND
# conv — prepare_quantized_conv routes through prepare_quantized_dense) bumps
# it. repro.prepare snapshots it to prove a warm start re-quantized nothing.
counters = {"prepare_dense": 0}


@dataclasses.dataclass(frozen=True)
class QuantParams:
    """Affine quantization: real = scale * (q - zero_point)."""
    scale: Array          # () or (channels,)
    zero_point: Array     # same shape as scale, stored int32
    dtype: jnp.dtype      # target integer dtype
    axis: Optional[int] = None  # channel axis, None = per-tensor


def d_bit_growth(a_signed: bool, b_signed: bool) -> int:
    """§4.1: d = 1 if a and b are both signed or both unsigned, else 2."""
    return 1 if a_signed == b_signed else 2


def preadd_bits(w: int, a_signed: bool, b_signed: bool) -> int:
    """§4.4: bits needed for the pre-add (a ± b sums): w + d."""
    return w + d_bit_growth(a_signed, b_signed)


def calibrate(x: Array, dtype=jnp.int8, *, symmetric: bool = True,
              axis: Optional[int] = None) -> QuantParams:
    """Min/max calibration producing QuantParams."""
    qmin, qmax = _INT_INFO[jnp.dtype(dtype)]
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis) if axis is not None else None
    if symmetric:
        amax = jnp.max(jnp.abs(x), axis=reduce_axes)
        # signed: +/-qmax around 0. unsigned: +/-(range/2) around midpoint zp.
        bound = qmax if qmin < 0 else (qmax - qmin) // 2
        scale = jnp.maximum(amax / bound, 1e-12)
        zp = (jnp.zeros_like(scale, jnp.int32) if qmin < 0
              else jnp.full_like(scale, (qmax + 1) // 2).astype(jnp.int32))
    else:
        xmin = jnp.min(x, axis=reduce_axes)
        xmax = jnp.max(x, axis=reduce_axes)
        scale = jnp.maximum((xmax - xmin) / (qmax - qmin), 1e-12)
        zp = jnp.clip(jnp.round(qmin - xmin / scale), qmin, qmax).astype(jnp.int32)
    return QuantParams(scale=scale, zero_point=zp, dtype=jnp.dtype(dtype), axis=axis)


def quantize(x: Array, qp: QuantParams) -> Array:
    qmin, qmax = _INT_INFO[qp.dtype]
    scale, zp = qp.scale, qp.zero_point
    if qp.axis is not None:
        shape = [1] * x.ndim
        shape[qp.axis] = -1
        scale = scale.reshape(shape)
        zp = zp.reshape(shape)
    q = jnp.round(x / scale) + zp
    return jnp.clip(q, qmin, qmax).astype(qp.dtype)


def dequantize(q: Array, qp: QuantParams) -> Array:
    scale, zp = qp.scale, qp.zero_point
    if qp.axis is not None:
        shape = [1] * q.ndim
        shape[qp.axis] = -1
        scale = scale.reshape(shape)
        zp = zp.reshape(shape)
    return (q.astype(jnp.int32) - zp).astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# Integer GEMM with zero-points — baseline and (F)FIP, bit-exact.
# ---------------------------------------------------------------------------

def int_gemm_baseline(aq: Array, bq: Array, za: Array, zb: Array) -> Array:
    """(A - za)(B - zb) in int32, the reference quantized GEMM."""
    a32 = aq.astype(jnp.int32) - za
    b32 = bq.astype(jnp.int32) - zb
    return jnp.matmul(a32, b32)


def zero_point_adjuster(aq: Array, zb: Array) -> Array:
    """Eq. (20) adjuster: AR_ij = zb_j * rowsum(A)_i, one multiply per element.

    The paper folds this into the alpha-generator row; here it is an explicit
    rank-1 term: outer(rowsum(A), zb). ``zb`` may be a per-tensor scalar or a
    per-channel ``(N,)`` vector of weight zero-points.
    """
    rowsum = jnp.sum(aq.astype(jnp.int32), axis=-1, keepdims=True)  # (..., M, 1)
    zb_vec = jnp.atleast_1d(jnp.asarray(zb, jnp.int32))             # (1,) or (N,)
    return rowsum * zb_vec                                          # (..., M, N)


def int_gemm_ffip(aq: Array, bq: Array, za: Array, zb: Array,
                  *, algo: str = "ffip") -> Array:
    """Quantized GEMM via FIP/FFIP with the paper's §3.3/§4.4 optimizations.

    Strategy (mirrors the hardware):
      * run (F)FIP on the RAW quantized integers (both-signed, d=1),
      * beta of the raw weights is folded into the bias offline (Eq. 15),
      * the zero-point contributions are removed via the adjuster (Eq. 20)
        plus the constant K*za*zb and za*colsum(B) terms,
    producing bit-exact int32 equality with :func:`int_gemm_baseline`.
    ``za`` is a per-tensor (or per-row ``(M, 1)``) activation zero-point;
    ``zb`` may be per-tensor or per-channel ``(N,)``.
    """
    k = aq.shape[-1]
    mm = fip.fip_matmul if algo == "fip" else fip.ffip_matmul
    raw = mm(aq.astype(jnp.int32), bq.astype(jnp.int32))       # A_q B_q
    # remove zero-point contributions:
    # (A-za)(B-zb) = AB - za*colsum(B) - zb*rowsum(A) + K*za*zb
    colsum_b = jnp.sum(bq.astype(jnp.int32), axis=0, keepdims=True)
    za = jnp.asarray(za, jnp.int32)
    zb = jnp.asarray(zb, jnp.int32)
    return raw - za * colsum_b - zero_point_adjuster(aq, zb) + k * za * zb


# ---------------------------------------------------------------------------
# Offline-prepared quantized dense layers — the serving decode path.
# ---------------------------------------------------------------------------

def prepare_quantized_dense(w: Array, *, dtype=jnp.int8,
                            symmetric: bool = False) -> dict:
    """Offline weight quantization for the serving path. ``w``: (..., K, N)
    (leading dims are stacked layer groups; each layer calibrates on its own).

    Per-output-channel affine quantization plus everything the paper computes
    once after training:
      * ``neg_beta``  — Eq. (15): -beta(W_q), folded into the integer bias so
        the FFIP beta subtraction costs nothing at inference,
      * ``colsum``    — colsum(W_q), the za-side zero-point term,
      * ``zp``        — per-channel zero-points consumed by the Eq. (20)
        adjuster at decode time.
    """
    counters["prepare_dense"] += 1
    return _prepare_quantized_dense(w, dtype=jnp.dtype(dtype),
                                    symmetric=symmetric)


# One compiled program per weight shape that maps over the stacked layers:
# run op by op over a whole stacked (L, K, N) weight, the f32 copies and pair
# slices need several times its size in device memory at once.
@functools.partial(jax.jit, static_argnames=("dtype", "symmetric"))
def _prepare_quantized_dense(w: Array, *, dtype, symmetric: bool) -> dict:
    one = functools.partial(_quantize_2d, dtype=dtype, symmetric=symmetric)
    if w.ndim == 2:
        return one(w)
    lead = w.shape[:-2]
    out = jax.lax.map(one, w.reshape(-1, *w.shape[-2:]))
    return jax.tree.map(lambda x: x.reshape(*lead, *x.shape[1:]), out)


def _quantize_2d(w: Array, *, dtype, symmetric: bool) -> dict:
    qmin, qmax = _INT_INFO[dtype]
    w = w.astype(jnp.float32)
    if symmetric:
        amax = jnp.max(jnp.abs(w), axis=-2)
        bound = qmax if qmin < 0 else (qmax - qmin) // 2
        scale = jnp.maximum(amax / bound, 1e-12)
        zp = (jnp.zeros_like(scale, jnp.int32) if qmin < 0
              else jnp.full_like(scale, (qmax + 1) // 2).astype(jnp.int32))
    else:
        wmin = jnp.min(w, axis=-2)
        wmax = jnp.max(w, axis=-2)
        scale = jnp.maximum((wmax - wmin) / (qmax - qmin), 1e-12)
        zp = jnp.clip(jnp.round(qmin - wmin / scale), qmin, qmax).astype(jnp.int32)
    qw = jnp.clip(jnp.round(w / scale[..., None, :]) + zp[..., None, :],
                  qmin, qmax).astype(dtype)
    q32 = qw.astype(jnp.int32)
    beta = jnp.sum(q32[..., 0::2, :] * q32[..., 1::2, :], axis=-2)  # Eq. (4)
    return {"qw": qw, "scale": scale, "zp": zp,
            "neg_beta": -beta, "colsum": jnp.sum(q32, axis=-2)}


def quantized_dense_apply(x: Array, q: dict, *, algo: str = "ffip") -> Array:
    """Apply a dense layer through its offline-prepared int8 weights.

    x: (M, K) float; q: per-layer dict from :func:`prepare_quantized_dense`
    (qw (K, N), scale/zp/neg_beta/colsum (N,)). Activations quantize
    dynamically PER TOKEN ROW (asymmetric int8) so a row's result never
    depends on what else is in the batch — continuous-batched decode stays
    bit-identical to sequential decode. Returns float32 (M, N) ~= x @ w.
    """
    qmin, qmax = _INT_INFO[jnp.int8.dtype]
    x32 = x.astype(jnp.float32)
    xmin = jnp.minimum(jnp.min(x32, axis=-1, keepdims=True), 0.0)
    xmax = jnp.maximum(jnp.max(x32, axis=-1, keepdims=True), 0.0)
    a_scale = jnp.maximum((xmax - xmin) / (qmax - qmin), 1e-12)    # (M, 1)
    a_zp = jnp.clip(jnp.round(qmin - xmin / a_scale),
                    qmin, qmax).astype(jnp.int32)                  # (M, 1)
    aq = jnp.clip(jnp.round(x32 / a_scale) + a_zp, qmin, qmax).astype(jnp.int8)

    a32 = aq.astype(jnp.int32)
    b32 = q["qw"].astype(jnp.int32)
    k = b32.shape[-2]
    if algo == "baseline":
        raw = jnp.matmul(a32, b32)                                 # A_q W_q
    elif algo == "ffip":
        # alpha is pair-swap invariant, so FFIP is the Eq. 16 form on the
        # pair-swapped operands with the same offline-folded beta
        raw = fip.fip_matmul_beta_folded(
            fip.pair_swap(a32), fip.pair_swap_rows(b32), q["neg_beta"])
    else:
        raw = fip.fip_matmul_beta_folded(a32, b32, q["neg_beta"])  # Eq. 15/16
    acc = (raw - a_zp * q["colsum"]                 # za * colsum(W_q)
           - zero_point_adjuster(aq, q["zp"])       # Eq. (20): zb_j * rowsum(A)_i
           + k * a_zp * q["zp"])
    return acc.astype(jnp.float32) * (a_scale * q["scale"])


def attach_quantized_weights(params, *, dtype=jnp.int8,
                             skip: Tuple[str, ...] = ("unembed",)) -> dict:
    """Walk a model param tree and attach a ``"q"`` entry (from
    :func:`prepare_quantized_dense`) next to every dense weight ``{"w": ...}``
    whose contraction dim is even. The added leaves carry the same leading
    stacked-layer dims as ``w``, so layer scans slice them transparently.
    Float weights/biases stay in place (gradients, fallback paths, logits —
    ``skip`` defaults to the unembed projection).
    """
    def walk(node):
        if not isinstance(node, dict):
            return node
        if "w" in node and not isinstance(node["w"], dict):
            w = node["w"]
            if w.ndim >= 2 and w.shape[-2] % 2 == 0:
                out = dict(node)
                out["q"] = prepare_quantized_dense(w, dtype=dtype)
                return out
            return node
        return {key: (val if key in skip else walk(val))
                for key, val in node.items()}

    return walk(params)


def quantized_dense_ffip(x: Array, w: Array, bias: Optional[Array],
                         xq: QuantParams, wq: QuantParams,
                         *, algo: str = "ffip") -> Array:
    """Full quantized dense layer: float in -> quant -> FFIP int GEMM -> dequant.

    beta folding: beta(W_q) is computed once from the quantized weights and
    folded into the integer bias (Eq. 15) — the (F)FIP beta subtraction then
    costs nothing at inference, exactly as in the paper.
    """
    aq = quantize(x, xq)
    bq = quantize(w, wq)
    k = aq.shape[-1]
    if k % 2 != 0:
        raise ValueError("pad K to even before quantized FFIP")
    a32 = aq.astype(jnp.int32)
    b32 = bq.astype(jnp.int32)
    beta_folded = fip.fold_beta_into_bias(b32)                    # -beta (Eq. 15)
    if algo == "ffip":
        raw = fip.fip_matmul_beta_folded(
            fip.pair_swap(a32), fip.pair_swap_rows(b32), beta_folded)
    else:
        raw = fip.fip_matmul_beta_folded(a32, b32, beta_folded)   # == A_q B_q
    colsum_b = jnp.sum(b32, axis=0, keepdims=True)
    acc = raw - xq.zero_point * colsum_b \
        - zero_point_adjuster(aq, wq.zero_point) \
        + k * xq.zero_point * wq.zero_point
    out = acc.astype(jnp.float32) * (xq.scale * wq.scale)
    if bias is not None:
        out = out + bias
    return out
