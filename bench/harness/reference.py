"""The parts of the plain reference that every family shares.

Each family module under ``bench/harness/families`` writes its decoder's
forward from the published equations with these parts: projections,
RMSNorm, rotary embedding in the rotate-half form and causal attention in
blocks of queries, in float32 with every matrix product at
``Precision.HIGHEST``. The reference imports nothing of the program: it reads
the weight tree the benchmark made, by name, and computes one sequence at a
time, layer by layer, so that it fits beside the weights.

``bits`` quantizes every projection as an integer tier states it: weights
per output channel and activations per token row, both asymmetric with the
activation range holding zero, and the product in plain integer arithmetic
(an int8 x int8 -> int32 matrix product plus the zero-point terms). The
unembedding stays float, as on the tiers. ``bits=8`` is the int8 tier's
reference; ``bits=4`` is the control one precision below it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block


def _quant(x, axis: int, bits: int):
    """Asymmetric quantization along ``axis`` (the reduced one): returns
    (q int8, scale, zero point) with real = scale * (q - zp)."""
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    lo = jnp.minimum(jnp.min(x, axis=axis, keepdims=True), 0.0)
    hi = jnp.maximum(jnp.max(x, axis=axis, keepdims=True), 0.0)
    scale = jnp.maximum((hi - lo) / (qmax - qmin), 1e-12)
    zp = jnp.clip(jnp.round(qmin - lo / scale), qmin, qmax)
    q = jnp.clip(jnp.round(x / scale) + zp, qmin, qmax)
    return q.astype(jnp.int8), scale, zp.astype(jnp.int32)


def _quant_weight(w, bits: int):
    """Per output channel, over the weight's own min and max."""
    qmin, qmax = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    lo = jnp.min(w, axis=0, keepdims=True)
    hi = jnp.max(w, axis=0, keepdims=True)
    scale = jnp.maximum((hi - lo) / (qmax - qmin), 1e-12)
    zp = jnp.clip(jnp.round(qmin - lo / scale), qmin, qmax)
    q = jnp.clip(jnp.round(w / scale) + zp, qmin, qmax)
    return q.astype(jnp.int8), scale, zp.astype(jnp.int32)


def proj(x, w, bits: int):
    """x (M, K) f32 @ w (K, N): float32, or integer at ``bits``."""
    w = w.astype(jnp.float32)
    if not bits:
        return jnp.matmul(x, w, precision=HI)
    aq, a_s, a_z = _quant(x, -1, bits)
    wq, w_s, w_z = _quant_weight(w, bits)
    raw = jax.lax.dot_general(aq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    rows = jnp.sum(aq.astype(jnp.int32), axis=1, keepdims=True)
    cols = jnp.sum(wq.astype(jnp.int32), axis=0, keepdims=True)
    k = x.shape[1]
    acc = raw - w_z * rows - a_z * cols + k * a_z * w_z
    return acc.astype(jnp.float32) * (a_s * w_s)


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, heads, hd); rotate-half rotary embedding at positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal attention; q (S, H, hd), k/v (S, KV, hd), in query blocks."""
    s, h, hd = q.shape
    group = h // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    nb = -(-s // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0)))
    kpos = jnp.arange(s)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, 0)
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / hd ** 0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(nb))
    return out.reshape(nb * Q_BLOCK, h, hd)[:s]
