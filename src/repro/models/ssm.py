"""State-space blocks: Mamba1 selective scan (falcon-mamba) and Mamba2 SSD
(zamba2), both with O(chunk) memory (no (B,S,d_inner,N) materialisation —
essential for the 32k prefill cells, see DESIGN.md §4).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Array = jax.Array


def _causal_conv(x: Array, w: Array, state: Optional[Array] = None,
                 ) -> Tuple[Array, Array]:
    """Depthwise causal conv1d. x: (B,S,C), w: (W,C). Returns (y, new_state)
    where state is the trailing (W-1) inputs for streaming decode."""
    width = w.shape[0]
    if state is None:
        x_pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    else:
        x_pad = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    new_state = x_pad[:, -(width - 1):, :] if width > 1 else x_pad[:, :0, :]
    y = sum(x_pad[:, i:i + x.shape[1], :] * w[i][None, None, :]
            for i in range(width))
    return y, new_state


# --- Mamba1 (selective scan) -------------------------------------------------

def mamba1_init(key, cfg: ModelConfig, dtype) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    dt_rank = s.dt_rank or -(-d // 16)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "in_proj": L.dense_init(k1, d, 2 * di, dtype),
        "conv_w": (jax.random.normal(k2, (s.d_conv, di), jnp.float32) * 0.1).astype(dtype),
        "x_proj": L.dense_init(k3, di, dt_rank + 2 * s.d_state, dtype),
        "dt_proj": L.dense_init(k4, dt_rank, di, dtype, bias=True),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, s.d_state + 1, dtype=jnp.float32), (di, s.d_state))).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": L.dense_init(k5, di, d, dtype),
    }


def _mamba1_scan(xz: Array, dt: Array, B: Array, C: Array, A: Array, D: Array,
                 h0: Array, chunk: int) -> Tuple[Array, Array]:
    """Selective scan, chunked over sequence to bound the (B,c,di,N) transient.

    xz: (Bt,S,di) conv+silu output; dt: (Bt,S,di); B,C: (Bt,S,N); A: (di,N).
    h0: (Bt,di,N) initial state. Returns (y (Bt,S,di), h_final).
    """
    bt, s, di = xz.shape
    n = A.shape[-1]
    n_chunks = max(1, s // chunk)
    assert s % n_chunks == 0
    xz_c = xz.reshape(bt, n_chunks, -1, di)
    dt_c = dt.reshape(bt, n_chunks, -1, di)
    b_c = B.reshape(bt, n_chunks, -1, n)
    c_c = C.reshape(bt, n_chunks, -1, n)

    def chunk_step(h, inp):
        xzk, dtk, bk, ck = inp                      # (Bt,c,di) / (Bt,c,N)
        da = jnp.exp(dtk[..., None] * A)            # (Bt,c,di,N) discretized A
        dbx = dtk[..., None] * bk[:, :, None, :] * xzk[..., None]

        def step(hh, t_inp):
            da_t, dbx_t = t_inp                     # (Bt,di,N)
            hh = da_t * hh + dbx_t
            return hh, hh

        h, hs = jax.lax.scan(step, h,
                             (jnp.moveaxis(da, 1, 0), jnp.moveaxis(dbx, 1, 0)))
        y = jnp.einsum("cbdn,bcn->bcd", hs, ck)     # (Bt,c,di)
        return h, y

    h, ys = jax.lax.scan(
        chunk_step, h0,
        (jnp.moveaxis(xz_c, 1, 0), jnp.moveaxis(dt_c, 1, 0),
         jnp.moveaxis(b_c, 1, 0), jnp.moveaxis(c_c, 1, 0)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bt, s, di)
    return y + xz * D, h


def mamba1_apply(p: dict, x: Array, *, cfg: ModelConfig,
                 cache: Optional[dict] = None, prefill: bool = False,
                 ) -> Tuple[Array, Optional[dict]]:
    """cache = {"conv": (B, W-1, di), "ssm": (B, di, N)} for streaming decode.

    prefill=True (forward-only) routes the recurrence through the fused
    Pallas selective-scan kernel (state in VMEM — §Perf falcon-mamba); train
    keeps the differentiable chunked scan."""
    s_cfg = cfg.ssm
    b, s, d = x.shape
    di = s_cfg.expand * d
    dt_rank = s_cfg.dt_rank or -(-d // 16)
    xz = L.dense(x, p["in_proj"])
    xs, z = jnp.split(xz, 2, axis=-1)
    conv_state = cache["conv"] if cache is not None else None
    xs, new_conv = _causal_conv(xs, p["conv_w"], conv_state)
    xs = jax.nn.silu(xs)
    proj = L.dense(xs, p["x_proj"])
    dt, bmat, cmat = jnp.split(proj, [dt_rank, dt_rank + s_cfg.d_state], axis=-1)
    dt = jax.nn.softplus(L.dense(dt, p["dt_proj"]))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    h0 = (cache["ssm"].astype(jnp.float32) if cache is not None
          else jnp.zeros((b, di, s_cfg.d_state), jnp.float32))
    if s > 1:
        # fused Pallas path: fwd-only (prefill) keeps h for the cache; train
        # uses the custom-VJP kernel pair (§Perf falcon-mamba iters 1-2)
        y, h = _selective_scan_fused(xs, dt, bmat, cmat, A, h0, s_cfg.chunk,
                                     trainable=not prefill)
        y = y.astype(jnp.float32) + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)
        if h is None:
            h = h0
    else:
        y, h = _mamba1_scan(xs.astype(jnp.float32), dt.astype(jnp.float32),
                            bmat.astype(jnp.float32), cmat.astype(jnp.float32),
                            A, p["D"].astype(jnp.float32), h0, s_cfg.chunk)
    out = L.dense((y.astype(x.dtype) * jax.nn.silu(z)), p["out_proj"])
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv.astype(cache["conv"].dtype), "ssm": h.astype(cache["ssm"].dtype)}
    return out, new_cache


def _selective_scan_fused(xs, dt, bmat, cmat, A, h0, chunk, *,
                          trainable: bool = False):
    """Fused Pallas selective scan, shard_mapped (B->data, d_inner->model).

    trainable=True uses the custom-VJP kernel pair (exact grads, chunk-
    checkpointed bwd recompute) and returns (y, None); otherwise returns
    (y, h_final) for the streaming-cache contract."""
    from repro.dist import context as dctx
    from repro.kernels import selective_scan as ssk
    import numpy as np
    mesh = dctx.get_mesh()
    b, s, di = xs.shape
    ck, bd = min(chunk, 128), min(512, di)
    if trainable:
        call = lambda x_, dt_, b_, c_, a_, h_: ssk.selective_scan_trainable(
            x_, dt_, b_, c_, a_, h_, ck, bd)
    else:
        call = lambda x_, dt_, b_, c_, a_, h_: ssk.selective_scan(
            x_, dt_, b_, c_, a_, h_, chunk=ck, bd=bd, interpret=None)[:2]
    if mesh is None:
        out = call(xs, dt, bmat, cmat, A, h0)
        return (out, None) if trainable else out
    from jax.sharding import PartitionSpec as P
    axis_size = dict(zip(mesh.axis_names, mesh.devices.shape))
    baxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bshard = baxes if b % int(np.prod([axis_size[a] for a in baxes] or [1])) == 0 else None
    dshard = "model" if ("model" in axis_size and di % axis_size["model"] == 0) else None
    sx = P(bshard, None, dshard)
    sn = P(bshard, None, None)
    in_specs = (sx, sx, sn, sn, P(dshard, None), P(bshard, dshard, None))
    out = jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                        out_specs=sx if trainable else (sx, P(bshard, dshard, None)),
                        check_vma=False)(xs, dt, bmat, cmat, A, h0)
    return (out, None) if trainable else out


# --- Mamba2 (SSD, scalar-per-head decay) -------------------------------------

def mamba2_init(key, cfg: ModelConfig, dtype) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    di = s.expand * d
    n_heads = di // s.head_dim
    bc_dim = 2 * s.n_groups * s.d_state
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    return {
        # PERF (§Perf zamba2 iter-2): separate projections instead of one fused
        # in_proj — the fused (2*di + 2*G*N + H)-wide output had split
        # boundaries misaligned with the model-axis shards, inducing an
        # all-gather per chunk step (7k all-gathers / 1.7TB wire per train
        # step). Separate x / BC / dt / z outputs shard cleanly, and the
        # depthwise conv splits per-channel into conv_x + conv_bc (identical
        # math, aligned shards).
        "z_proj": L.dense_init(k1, d, di, dtype),
        "x_proj_in": L.dense_init(k5, d, di, dtype),
        "bc_proj": L.dense_init(k7, d, bc_dim, dtype),
        "dtp": L.dense_init(k6, d, n_heads, dtype),
        "conv_x": (jax.random.normal(k2, (s.d_conv, di), jnp.float32) * 0.1).astype(dtype),
        "conv_bc": (jax.random.normal(k3, (s.d_conv, bc_dim), jnp.float32) * 0.1).astype(dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_heads)).astype(dtype),
        "D": jnp.ones((n_heads,), dtype),
        "dt_bias": jnp.zeros((n_heads,), dtype),
        "norm": L.rmsnorm_init(di, dtype),
        "out_proj": L.dense_init(k4, di, d, dtype),
    }


def _segsum(log_a: Array) -> Array:
    """(..., C) -> (..., C, C) lower-triangular cumulative log-decay sums."""
    c = log_a.shape[-1]
    cums = jnp.cumsum(log_a, axis=-1)
    diff = cums[..., :, None] - cums[..., None, :]
    mask = jnp.tril(jnp.ones((c, c), bool))
    return jnp.where(mask, diff, -jnp.inf)


def _ssd_chunked(xh: Array, dt: Array, log_a: Array, B: Array, C: Array,
                 h0: Array, chunk: int) -> Tuple[Array, Array]:
    """Mamba2 SSD. xh: (Bt,S,H,P); dt,log_a contributions: (Bt,S,H);
    B,C: (Bt,S,G,N); h0: (Bt,H,P,N). Sequential scan over chunks, the
    intra-chunk term is the attention-like einsum of the SSD paper."""
    bt, s, h, p_ = xh.shape
    g, n = B.shape[2], B.shape[3]
    heads_per_g = h // g
    n_chunks = max(1, s // chunk)
    assert s % n_chunks == 0
    c = s // n_chunks

    def rs(t):
        return t.reshape(bt, n_chunks, c, *t.shape[2:])

    xh_c, dt_c, la_c = rs(xh), rs(dt), rs(log_a)
    b_c, c_c = rs(B), rs(C)

    # PERF (§Perf zamba2 iter-4): intra-chunk tensors in the model compute
    # dtype (bf16 in production), state carry in f32 — halves chunk bytes.
    cdt = xh.dtype

    def chunk_step(hstate, inp):
        xk, dtk, lak, bk, ck = inp
        # PERF (EXPERIMENTS.md §Perf zamba2 iter-1): fold every scalar factor
        # (dt, segment decays) into x/C BEFORE the contractions so all einsums
        # are clean 2-operand dots. Multi-operand einsums with per-(b,c,h)
        # scalar operands made jax materialize (B,c,H,P,N) 5-D intermediates
        # in the BACKWARD pass (~430TB/step for the zamba2 train cell).
        seg = _segsum(jnp.moveaxis(lak, 1, 2))          # (Bt,H,c,c) f32
        decay = jnp.exp(seg)
        bk_h = jnp.repeat(bk, heads_per_g, axis=2)      # (Bt,c,H,N)
        ck_h = jnp.repeat(ck, heads_per_g, axis=2)
        xdt = (xk * dtk[..., None].astype(cdt))         # (Bt,c,H,P) dt folded
        scores = jnp.einsum("bqhn,bkhn->bhqk", ck_h, bk_h,
                            preferred_element_type=jnp.float32)
        scores = (scores * decay).astype(cdt)
        intra = jnp.einsum("bhqk,bkhp->bqhp", scores, xdt,
                           preferred_element_type=jnp.float32)
        # inter-chunk: carry-in state contribution + state update
        cum = jnp.cumsum(lak, axis=1)                   # (Bt,c,H) f32
        c_scaled = ck_h * jnp.exp(cum)[..., None].astype(cdt)
        inter = jnp.einsum("bqhn,bhpn->bqhp", c_scaled.astype(jnp.float32),
                           hstate)
        total_decay = jnp.exp(cum[:, -1])               # (Bt,H)
        x_tail = xdt * jnp.exp(cum[:, -1][:, None] - cum)[..., None].astype(cdt)
        new_state = (hstate * total_decay[..., None, None]
                     + jnp.einsum("bkhp,bkhn->bhpn", x_tail, bk_h,
                                  preferred_element_type=jnp.float32))
        return new_state, intra + inter

    h_fin, ys = jax.lax.scan(
        chunk_step, h0,
        tuple(jnp.moveaxis(t, 1, 0) for t in (xh_c, dt_c, la_c, b_c, c_c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bt, s, h, p_)
    return y, h_fin


def mamba2_apply(p: dict, x: Array, *, cfg: ModelConfig,
                 cache: Optional[dict] = None,
                 ) -> Tuple[Array, Optional[dict]]:
    """cache = {"conv": (B,W-1,conv_dim), "ssm": (B,H,P,N)}."""
    s_cfg = cfg.ssm
    b, s, d = x.shape
    di = s_cfg.expand * d
    hdim = s_cfg.head_dim
    n_heads = di // hdim
    g, n = s_cfg.n_groups, s_cfg.d_state
    z = L.dense(x, p["z_proj"])
    xin = L.dense(x, p["x_proj_in"])
    bc = L.dense(x, p["bc_proj"])
    dt = L.dense(x, p["dtp"])
    xs, new_conv_x = _causal_conv(xin, p["conv_x"],
                                  cache["conv"] if cache is not None else None)
    bc, new_conv_bc = _causal_conv(bc, p["conv_bc"],
                                   cache["conv_bc"] if cache is not None else None)
    xs = jax.nn.silu(xs)
    bc = jax.nn.silu(bc)
    bmat, cmat = jnp.split(bc, [g * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    log_a = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt        # (B,S,H)
    xh = xs.reshape(b, s, n_heads, hdim)           # model dtype (bf16 prod)
    bmat = bmat.reshape(b, s, g, n)
    cmat = cmat.reshape(b, s, g, n)
    h0 = (cache["ssm"].astype(jnp.float32) if cache is not None
          else jnp.zeros((b, n_heads, hdim, n), jnp.float32))
    y, h = _ssd_chunked(xh, dt, log_a, bmat, cmat, h0, s_cfg.chunk)
    y = y + (xh * p["D"][None, None, :, None].astype(xh.dtype)).astype(y.dtype)
    y = y.reshape(b, s, di).astype(x.dtype) * jax.nn.silu(z)
    out = L.dense(L.rmsnorm(y, p["norm"], cfg.norm_eps), p["out_proj"])
    new_cache = None
    if cache is not None:
        new_cache = {"conv": new_conv_x.astype(cache["conv"].dtype),
                     "conv_bc": new_conv_bc.astype(cache["conv_bc"].dtype),
                     "ssm": h.astype(cache["ssm"].dtype)}
    return out, new_cache
