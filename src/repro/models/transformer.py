"""Model stacks: decoder-only (dense/MoE/MLA), SSM, hybrid, and enc-dec.

All stacks scan over layers with stacked parameters so HLO size is
depth-independent (62-layer models compile like 2-layer ones). Per-layer
heterogeneity (gemma3 local:global windows/thetas, mixtral SWA) is carried as
scanned (L,)-arrays, never by unrolling.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as S

Array = jax.Array
PyTree = Any


def norm_init(cfg: ModelConfig, dtype):
    return (L.layernorm_init(cfg.d_model, dtype) if cfg.norm == "layernorm"
            else L.rmsnorm_init(cfg.d_model, dtype))


def norm_apply(x, p, cfg: ModelConfig):
    return (L.layernorm(x, p, cfg.norm_eps) if cfg.norm == "layernorm"
            else L.rmsnorm(x, p, cfg.norm_eps))


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, dtype, *, kind: str) -> dict:
    """kind encodes attention x ffn: dense | moe | mla_moe | mla_dense |
    ssm1 | ssm2 | encdec | encoder. '*moe' kinds take the MoE FFN; 'mla*'
    kinds take MLA attention."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: dict = {"ln1": norm_init(cfg, dtype)}
    if kind.startswith("mla"):
        p["attn"] = A.mla_init(k1, cfg, dtype)
    elif kind == "ssm1":
        p["ssm"] = S.mamba1_init(k1, cfg, dtype)
        return p
    elif kind == "ssm2":
        p["ssm"] = S.mamba2_init(k1, cfg, dtype)
        return p
    else:
        p["attn"] = A.gqa_init(k1, cfg, dtype)
    p["ln2"] = norm_init(cfg, dtype)
    if kind.endswith("moe"):
        p["ffn"] = MOE.moe_init(k2, cfg, dtype)
    else:
        d_ff = cfg.d_ff
        p["ffn"] = L.mlp_init(k2, cfg.d_model, d_ff, dtype)
    if kind == "encdec":
        p["ln_x"] = norm_init(cfg, dtype)
        p["xattn"] = A.cross_init(k3, cfg, dtype)
    return p


def block_apply(p: dict, x: Array, *, cfg: ModelConfig, kind: str,
                positions: Array, window=0, theta=None, causal: bool = True,
                cache: Optional[dict] = None, layer=None, cache_pos=None,
                cache_write_mask: Optional[Array] = None,
                enc: Optional[Array] = None,
                cross_kv: Optional[dict] = None, prefill: bool = False,
                page_table: Optional[Array] = None,
                paged_impl: str = "gather",
                ) -> Tuple[Array, Optional[dict], Array]:
    """Returns (x, new_cache, aux_loss).

    ``cache`` holds the layer group's caches stacked on the layer axis and
    ``layer`` is this layer's index in them; the returned caches are the
    same stacks with this layer's state updated."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("ssm1", "ssm2"):
        if page_table is not None:
            raise ValueError("paged KV cache requires attention layers; "
                             f"got layer kind {kind!r}")
        # an SSM layer's state is a running summary, replaced whole each call
        state = None if cache is None else jax.tree.map(
            lambda t: A.LayerSlot(t, layer).read(), cache)
        if kind == "ssm1":
            h, new_state = S.mamba1_apply(p["ssm"], norm_apply(x, p["ln1"], cfg),
                                          cfg=cfg, cache=state, prefill=prefill)
        else:
            h, new_state = S.mamba2_apply(p["ssm"], norm_apply(x, p["ln1"], cfg),
                                          cfg=cfg, cache=state)
        new_cache = None if cache is None else jax.tree.map(
            lambda t, n: jax.lax.dynamic_update_index_in_dim(
                t, n.astype(t.dtype), layer, 0), cache, new_state)
        return x + h, new_cache, aux

    attn_fn = (functools.partial(A.mla_apply, prefill=prefill)
               if kind.startswith("mla") else functools.partial(
                   A.gqa_apply, rope_theta=theta, causal=causal,
                   prefill=prefill))
    h, new_cache = attn_fn(p["attn"], norm_apply(x, p["ln1"], cfg), cfg=cfg,
                           positions=positions, window=window, cache=cache,
                           layer=layer, cache_pos=cache_pos,
                           cache_write_mask=cache_write_mask,
                           page_table=page_table, paged_impl=paged_impl)
    x = x + h
    if kind == "encdec":
        xh = A.cross_apply(p["xattn"], norm_apply(x, p["ln_x"], cfg),
                           enc, cfg) if cross_kv is None else \
            _cross_from_kv(p["xattn"], norm_apply(x, p["ln_x"], cfg), cross_kv, cfg)
        x = x + xh
    h2 = norm_apply(x, p["ln2"], cfg)
    if kind.endswith("moe"):
        f, aux = MOE.moe_apply(p["ffn"], h2, cfg=cfg)
    else:
        f = L.mlp(h2, p["ffn"], cfg.act)
    return x + f, new_cache, aux


def _cross_from_kv(p, x, cross_kv, cfg):
    """Cross-attention against cached encoder K/V (decode path)."""
    b, s, d = x.shape
    hd = cfg.hd
    q = L.dense(x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    out = A._sdpa(q, cross_kv["k"], cross_kv["v"], None)
    return L.dense(out.reshape(b, s, cfg.n_heads * hd), p["wo"])


def make_cross_kv(p_stacked: dict, enc: Array, cfg: ModelConfig) -> dict:
    """Precompute per-layer cross K/V from encoder output (prefill)."""
    def one(p):
        b, t, _ = enc.shape
        k = L.dense(enc, p["xattn"]["wk"]).reshape(b, t, cfg.n_kv_heads, cfg.hd)
        v = L.dense(enc, p["xattn"]["wv"]).reshape(b, t, cfg.n_kv_heads, cfg.hd)
        return {"k": k, "v": v}
    return jax.lax.map(one, p_stacked)


# ---------------------------------------------------------------------------
# Layer plans: what kind each scan-group is, plus per-layer window/theta arrays
# ---------------------------------------------------------------------------

def layer_plan(cfg: ModelConfig):
    """Returns list of (group_name, kind, n_layers). Scans run per group."""
    if cfg.family == "ssm":
        return [("layers", "ssm1" if cfg.ssm.version == 1 else "ssm2", cfg.n_layers)]
    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period or cfg.n_layers
        n_full = cfg.n_layers // period
        rem = cfg.n_layers - n_full * period
        plan = [("hybrid_groups", "ssm2", n_full * period)]
        if rem:
            plan.append(("tail", "ssm2", rem))
        return plan
    if cfg.family == "moe":
        plan = []
        if cfg.first_k_dense:
            plan.append(("dense_head", "mla_dense" if cfg.mla else "dense",
                         cfg.first_k_dense))
        plan.append(("layers", "mla_moe" if cfg.mla else "moe",
                     cfg.n_layers - cfg.first_k_dense))
        return plan
    if cfg.family == "enc-dec":
        return [("layers", "encdec", cfg.n_layers)]
    return [("layers", "dense", cfg.n_layers)]


def window_theta_arrays(cfg: ModelConfig, n: int, offset: int = 0):
    """(window, theta) per layer as numpy arrays for the scan."""
    win = np.zeros((n,), np.int32)
    theta = np.full((n,), cfg.rope_theta, np.float32)
    for i in range(n):
        li = i + offset
        if cfg.local_global_period:
            is_global = (li + 1) % cfg.local_global_period == 0
            win[i] = 0 if is_global else cfg.sliding_window
            theta[i] = (cfg.rope_theta_global or cfg.rope_theta) if is_global \
                else cfg.rope_theta
        elif cfg.sliding_window:
            win[i] = cfg.sliding_window
    return jnp.asarray(win), jnp.asarray(theta)


# ---------------------------------------------------------------------------
# Whole-model init
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> PyTree:
    dtype = cfg.dtype
    keys = jax.random.split(key, 8)
    params: Dict[str, PyTree] = {
        "embed": L.embed_init(keys[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": norm_init(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(keys[1], cfg.d_model, cfg.vocab, dtype)

    def stacked(key, n, kind):
        ks = jax.random.split(key, n)
        return jax.vmap(lambda k: block_init(k, cfg, dtype, kind=kind))(ks)

    for gi, (name, kind, n) in enumerate(layer_plan(cfg)):
        params[name] = stacked(keys[2 + gi], n, kind)

    if cfg.family == "hybrid" and cfg.hybrid_attn_period:
        params["shared_attn"] = {
            "ln": norm_init(cfg, dtype),
            "attn": A.gqa_init(keys[6], cfg, dtype),
        }
    if cfg.encoder is not None:
        ks = jax.random.split(keys[7], cfg.encoder.n_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: block_init(k, cfg, dtype, kind="encoder"))(ks),
            "norm": norm_init(cfg, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Forward passes (training / prefill / decode share one scan machinery)
# ---------------------------------------------------------------------------

def _maybe_remat(body, cfg: ModelConfig):
    """Per-layer rematerialisation policy for the layer scans (train memory)."""
    if cfg.remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if cfg.remat == "full":
        return jax.checkpoint(body)
    return body


def _scan_group(p_stacked, x, *, cfg, kind, positions, windows=None,
                thetas=None, causal=True, caches=None, cache_pos=None,
                cache_write_mask=None, enc=None, cross_kvs=None,
                prefill=False, page_table=None, paged_impl="gather"):
    """lax.scan over a stacked layer group. caches/cross_kvs are stacked on
    the leading (layer) axis when present.

    The caches ride in the scan's carry, not its xs/ys: each layer writes its
    new rows (or, for an SSM layer, its new state) into the stacks at its
    own index in place and reads its K/V from there, so a donated cache
    aliases the program's output and no layer-sized slice is copied out or
    stacked back. ``cross_kvs`` are only read, and stay in xs."""
    n = jax.tree_util.tree_leaves(p_stacked)[0].shape[0]
    if windows is None:
        windows = jnp.zeros((n,), jnp.int32)
    if thetas is None:
        thetas = jnp.full((n,), cfg.rope_theta, jnp.float32)

    def body(carry, xs):
        x, aux_acc, c = carry
        p, w, th, i, ckv = xs
        x, c, aux = block_apply(
            p, x, cfg=cfg, kind=kind, positions=positions, window=w, theta=th,
            causal=causal, cache=c, layer=i, cache_pos=cache_pos,
            cache_write_mask=cache_write_mask, enc=enc,
            cross_kv=ckv, prefill=prefill, page_table=page_table,
            paged_impl=paged_impl)
        return (x, aux_acc + aux, c), None

    body = _maybe_remat(body, cfg)
    xs = (p_stacked, windows, thetas, jnp.arange(n, dtype=jnp.int32),
          cross_kvs)
    (x, aux, new_caches), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), caches), xs)
    return x, aux, new_caches


def _hybrid_forward(params, x, *, cfg, positions, caches=None, cache_pos=None,
                    prefill=False):
    """Zamba2: groups of `period` mamba2 layers, shared attn after each group."""
    period = cfg.hybrid_attn_period
    n_full = cfg.n_layers // period
    aux_total = jnp.zeros((), jnp.float32)

    p_groups = jax.tree.map(
        lambda t: t.reshape(n_full, period, *t.shape[1:]), params["hybrid_groups"])
    sa = params.get("shared_attn")

    def group_body(carry, xs):
        x, _ = carry
        p_grp, c_grp, sa_cache = xs if caches is not None else (xs, None, None)
        x, aux, new_c = _scan_group(p_grp, x, cfg=cfg, kind="ssm2",
                                    positions=positions, caches=c_grp,
                                    cache_pos=cache_pos)
        # the shared layer's cache is scanned over, one layer at a time: a
        # stack of one for gqa_apply
        one = lambda tree: jax.tree.map(lambda t: t[None], tree)
        h, new_sa = A.gqa_apply(sa["attn"], norm_apply(x, sa["ln"], cfg),
                                cfg=cfg, positions=positions, window=0,
                                cache=one(sa_cache), layer=0,
                                cache_pos=cache_pos, prefill=prefill)
        new_sa = jax.tree.map(lambda t: t[0], new_sa)
        x = x + h
        return (x, aux), (new_c, new_sa)

    group_body = _maybe_remat(group_body, cfg)
    if caches is not None:
        xs = (p_groups, caches["hybrid_groups"], caches["shared_attn"])
    else:
        xs = p_groups
    (x, aux), outs = jax.lax.scan(group_body, (x, aux_total), xs)
    new_caches = {}
    if caches is not None:
        new_caches["hybrid_groups"], new_caches["shared_attn"] = outs
    if "tail" in params:
        tail_c = caches["tail"] if caches is not None else None
        x, aux2, new_tail = _scan_group(params["tail"], x, cfg=cfg, kind="ssm2",
                                        positions=positions, caches=tail_c,
                                        cache_pos=cache_pos)
        if caches is not None:
            new_caches["tail"] = new_tail
    return x, aux, (new_caches if caches is not None else None)


def encode(params, frames: Array, cfg: ModelConfig) -> Array:
    """Whisper encoder over (stub) precomputed frame embeddings."""
    t = frames.shape[1]
    positions = jnp.arange(t, dtype=jnp.int32)
    x, _, _ = _scan_group(params["encoder"]["layers"], frames, cfg=cfg,
                          kind="encoder", positions=positions, causal=False)
    return norm_apply(x, params["encoder"]["norm"], cfg)


def forward(params, tokens: Array, cfg: ModelConfig, *,
            frames: Optional[Array] = None,
            patches: Optional[Array] = None,
            caches: Optional[dict] = None, cache_pos=None,
            cache_write_mask: Optional[Array] = None,
            is_prefill: bool = False,
            page_table: Optional[Array] = None,
            paged_impl: str = "gather",
            ) -> Tuple[Array, Array, Optional[dict]]:
    """Token ids -> final hidden states. Returns (hidden, aux_loss, new_caches).

    * train/prefill: caches=None / caches=zeros, full sequence.
    * decode: tokens (B,1), caches + cache_pos set.
    * cache_write_mask: optional (B,) bool — batch rows with False leave the
      cache untouched (bucketed prefill runs over the SHARED slot cache and
      only commits the admitted rows; live slots keep their K/V). With a
      page table it may also be (B, S) bool — per-token masks for a padded
      prefill chunk's tail.
    * page_table: optional (B, max_pages) int32 — caches hold PAGE POOLS (see
      init_paged_cache) and attention layers address them through the table;
      paged_impl selects "gather" (bit-exact oracle) or "flash" (in-kernel
      gather).
    * frames: whisper encoder stub embeddings; patches: vlm prefix embeddings.
    """
    x = L.embed(tokens, params["embed"])
    b, s = tokens.shape[:2]
    n_prefix = 0
    if patches is not None:   # vlm prefix (train + prefill; decode passes None)
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        n_prefix = patches.shape[1]
        s = x.shape[1]
    if cache_pos is not None:
        # cache_pos: scalar (shared offset — prefill / legacy decode) or a
        # (B,) per-slot position vector (continuous-batching decode).
        cp = jnp.asarray(cache_pos, jnp.int32)
        if cp.ndim == 1:
            positions = cp[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        else:
            positions = cp + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
    else:
        positions = jnp.arange(s, dtype=jnp.int32)

    enc = None
    cross_kvs = None
    if cfg.encoder is not None:
        if frames is not None:
            enc = encode(params, frames, cfg)
            if caches is not None:   # prefill: cache per-layer cross K/V
                cross_kvs = make_cross_kv(params["layers"], enc, cfg)
        else:
            cross_kvs = caches["cross_kv"]   # decode: reuse cached cross K/V

    aux_total = jnp.zeros((), jnp.float32)
    new_caches: Optional[dict] = {} if caches is not None else None

    if cfg.family == "hybrid":
        if page_table is not None:
            raise ValueError("paged KV cache is not supported for hybrid "
                             "(SSM-state) stacks")
        x, aux_total, new_caches = _hybrid_forward(
            params, x, cfg=cfg, positions=positions, caches=caches,
            cache_pos=cache_pos, prefill=is_prefill)
    else:
        offset = 0
        for name, kind, n in layer_plan(cfg):
            win, theta = window_theta_arrays(cfg, n, offset)
            grp_cache = caches.get(name) if caches is not None else None
            grp_cross = cross_kvs if kind == "encdec" else None
            x, aux, new_c = _scan_group(
                p_stacked=params[name], x=x, cfg=cfg, kind=kind,
                positions=positions, windows=win, thetas=theta,
                caches=grp_cache, cache_pos=cache_pos,
                cache_write_mask=cache_write_mask, enc=enc,
                cross_kvs=grp_cross, prefill=is_prefill,
                page_table=page_table, paged_impl=paged_impl)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches[name] = new_c
            offset += n

    x = norm_apply(x, params["final_norm"], cfg)
    if new_caches is not None and cross_kvs is not None:
        new_caches["cross_kv"] = cross_kvs
    if n_prefix:
        x = x[:, n_prefix:]
    return x, aux_total, new_caches


def logits_fn(params, hidden: Array, cfg: ModelConfig) -> Array:
    if cfg.tie_embeddings:
        return L.unembed(hidden, params["embed"])
    return L.dense(hidden, params["unembed"])


def sample_fn(params, hidden: Array, cfg: ModelConfig) -> Array:
    """Greedy sampling fused into the device program: unembed + argmax in one
    trace, so only (..., ) int32 token ids ever cross to the host — never the
    (..., V) float logits (the serving hot path's per-step host transfer drops
    from B×V floats to B int32s)."""
    return jnp.argmax(logits_fn(params, hidden, cfg), axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> dict:
    """Zero caches, stacked per layer group (shapes match forward's scans).

    Attention leaves hold their rows in blocks, (L, B, J, *feat, blk) with
    J * blk = max_len (see attention.row_block)."""
    dtype = dtype or cfg.dtype
    caches: Dict[str, PyTree] = {}
    blk = A.row_block(max_len)

    def rows(n, *feat):
        return jnp.zeros((n, batch, max_len // blk) + feat + (blk,), dtype)

    def kv(n):
        return {"k": rows(n, cfg.n_kv_heads, cfg.hd),
                "v": rows(n, cfg.n_kv_heads, cfg.hd)}

    def mla_c(n):
        m = cfg.mla
        return {"c_kv": rows(n, m.kv_lora_rank),
                "k_rope": rows(n, m.rope_head_dim)}

    def ssm_c(n):
        s = cfg.ssm
        di = s.expand * cfg.d_model
        if s.version == 1:
            return {"conv": jnp.zeros((n, batch, s.d_conv - 1, di), dtype),
                    "ssm": jnp.zeros((n, batch, di, s.d_state), jnp.float32)}
        bc_dim = 2 * s.n_groups * s.d_state
        n_heads = di // s.head_dim
        return {"conv": jnp.zeros((n, batch, s.d_conv - 1, di), dtype),
                "conv_bc": jnp.zeros((n, batch, s.d_conv - 1, bc_dim), dtype),
                "ssm": jnp.zeros((n, batch, n_heads, s.head_dim, s.d_state),
                                 jnp.float32)}

    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period
        n_full = cfg.n_layers // period
        rem = cfg.n_layers - n_full * period
        grp = ssm_c(n_full * period)
        caches["hybrid_groups"] = jax.tree.map(
            lambda t: t.reshape(n_full, period, *t.shape[1:]), grp)
        caches["shared_attn"] = kv(n_full)
        if rem:
            caches["tail"] = ssm_c(rem)
        return caches

    for name, kind, n in layer_plan(cfg):
        if kind in ("ssm1", "ssm2"):
            caches[name] = ssm_c(n)
        elif kind.startswith("mla"):
            caches[name] = mla_c(n)
        else:
            caches[name] = kv(n)
    return caches


def paged_cache_supported(cfg: ModelConfig) -> bool:
    """True iff every cached layer is a (GQA or MLA) attention layer — SSM
    states and encoder cross-KV have no per-token rows to page."""
    if cfg.family in ("ssm", "hybrid") or cfg.encoder is not None:
        return False
    return all(kind not in ("ssm1", "ssm2") for _, kind, _ in layer_plan(cfg))


def init_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     dtype=None) -> dict:
    """Zero page POOLS, stacked per layer group.

    Leaves mirror :func:`init_cache` but replace the (batch, max_len) row
    plane with a single shared (num_pages, page_size) pool: pool pages are
    batch-agnostic, so one pool serves the B-way decode batch and batch-1
    prefill chunks simultaneously, and two sequences can reference the same
    page (refcounted prefix sharing — serve/paged.py owns the allocator).
    """
    dtype = dtype or cfg.dtype
    if not paged_cache_supported(cfg):
        raise ValueError("paged KV cache requires a pure-attention decoder "
                         f"stack (family={cfg.family!r})")
    caches: Dict[str, PyTree] = {}

    def kv(n):
        shp = (n, num_pages, page_size, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}

    def mla_c(n):
        m = cfg.mla
        return {"c_kv": jnp.zeros((n, num_pages, page_size, m.kv_lora_rank),
                                  dtype),
                "k_rope": jnp.zeros((n, num_pages, page_size,
                                     m.rope_head_dim), dtype)}

    for name, kind, n in layer_plan(cfg):
        caches[name] = mla_c(n) if kind.startswith("mla") else kv(n)
    return caches
