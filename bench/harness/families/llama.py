"""The Llama family: a decoder of Llama layers (RMSNorm, rotary embedding in
the rotate-half form, grouped-query causal attention, SwiGLU MLP), read from
a configuration file's ``model`` section in Hugging Face key names.

Everything the benchmark needs to know of a family lives in its module under
``bench/harness/families``, which the harness loads by the name the
configuration file gives under ``family``:

* ``model_config(config)``: the program's ``ModelConfig``, every size from
  the file;
* ``ModelShapes``: the work a step needs, counted from the shapes alone
  (``from_model``, ``vocab``, ``param_count``, ``prefill_flops``,
  ``decode_flops``, ``decode_bytes``, ``weight_bytes``,
  ``kv_bytes_per_token``);
* ``model_key(m)`` and ``token_gaps``: the plain reference, in float32 (or
  at an integer width), over the weight tree the benchmark made;
* ``INITIALIZERS``: ``{leaf name: fn(key, shape, dtype)}`` for parameter
  leaves beyond ``w``, ``table``, ``scale`` and ``b`` (none here).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness.reference import HI, attention, proj, rmsnorm, rope

INITIALIZERS: dict = {}


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: every size
    from the file, nothing from the program's own registry."""
    from repro.configs.base import ModelConfig
    m = config["model"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        head_dim=m.get("head_dim", 0), rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]), act=m["hidden_act"],
        param_dtype=m["torch_dtype"])


@dataclasses.dataclass(frozen=True)
class ModelShapes:
    """Work the model needs, counted from configuration shapes alone:
    ``2*M*K*N`` per projection over the useful rows, causal attention over
    the rows a token attends, bytes of the weights a step reads plus the
    live K/V rows."""
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def from_model(cls, m: dict) -> "ModelShapes":
        """From a configuration file's ``model`` section (HF key names)."""
        d, h = m["hidden_size"], m["num_attention_heads"]
        return cls(d=d, layers=m["num_hidden_layers"], heads=h,
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or d // h,
                   d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                   tied=bool(m["tie_word_embeddings"]))

    # -- parameters ---------------------------------------------------------
    @property
    def layer_proj_params(self) -> int:
        """Weights of one layer's projections (q, k, v, o, gate, up, down)."""
        attn = (self.d * self.head_dim * (self.heads + 2 * self.kv_heads)
                + self.heads * self.head_dim * self.d)
        return attn + 3 * self.d * self.d_ff

    @property
    def proj_params(self) -> int:
        return self.layers * self.layer_proj_params

    @property
    def layer_proj_channels(self) -> int:
        """Output channels of one layer's projections."""
        return (self.head_dim * (self.heads + 2 * self.kv_heads)
                + 2 * self.d + 2 * self.d_ff)

    @property
    def unembed_params(self) -> int:
        return self.d * self.vocab

    def param_count(self) -> int:
        embed = self.vocab * self.d
        head = 0 if self.tied else self.d * self.vocab
        norms = self.layers * 2 * self.d + self.d
        return embed + head + self.proj_params + norms

    # -- operations -----------------------------------------------------------
    def attn_flops(self, pairs: int) -> int:
        """QK^T and PV over ``pairs`` (query, key) pairs, all layers."""
        return self.layers * 4 * self.heads * self.head_dim * pairs

    def prefill_flops(self, n: int) -> int:
        """One prompt of ``n`` tokens: every projection over its rows, causal
        attention, and the unembedding of the one row that is sampled."""
        return (2 * n * self.proj_params + self.attn_flops(n * (n + 1) // 2)
                + 2 * self.unembed_params)

    def decode_flops(self, ctx: int) -> int:
        """One decoded token that attends ``ctx`` cached rows (itself
        included)."""
        return (2 * self.proj_params + self.attn_flops(ctx)
                + 2 * self.unembed_params)

    # -- bytes ---------------------------------------------------------------
    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * 2

    def weight_bytes(self, tier: str) -> int:
        """Weights one decode step must read: the projections (int8 plus
        four 4-byte per-channel vectors on the int8 tier, bf16 otherwise)
        and the bf16 unembedding, which every tier keeps in float."""
        if tier == "int8":
            proj_b = (self.proj_params
                      + 16 * self.layers * self.layer_proj_channels)
        else:
            proj_b = 2 * self.proj_params
        return proj_b + 2 * self.unembed_params

    def decode_bytes(self, tier: str, ctx_rows: int, new_rows: int) -> int:
        """One decode dispatch: the weights once, ``ctx_rows`` live K/V rows
        read over all slots, ``new_rows`` rows written."""
        return (self.weight_bytes(tier)
                + (ctx_rows + new_rows) * self.kv_bytes_per_token)


@functools.partial(jax.jit, static_argnames=("model", "bits", "n_out"))
def token_gaps(params, tokens, n_prompt, served, *, model: tuple, bits: int,
               n_out: int):
    """Reference logits at each served position and, for each served token,
    the gap by which its logit lies below the reference's best.

    A Llama decoder written from the published equations, in float32 with
    every matrix product at ``Precision.HIGHEST`` (or at ``bits``, see
    :func:`harness.reference.proj`), one sequence at a time, layer by layer
    (one layer's float32 weights at a time), attention in blocks of queries.

    tokens: (S,) prompt then served tokens, padded; n_prompt: () int32;
    served: (n_out,) the served tokens (padded with 0). Returns (gaps
    (n_out,), best (n_out,) the reference's own argmax): gaps[j] is for the
    token served at position n_prompt - 1 + j."""
    heads, kv_heads, hd, theta, eps, tied = model
    s = tokens.shape[0]
    pos = jnp.arange(s)
    x = params["embed"]["table"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = p["attn"]
        h = rmsnorm(x, p["ln1"]["scale"], eps)
        q = proj(h, a["wq"]["w"], bits).reshape(s, heads, hd)
        k = proj(h, a["wk"]["w"], bits).reshape(s, kv_heads, hd)
        v = proj(h, a["wv"]["w"], bits).reshape(s, kv_heads, hd)
        o = attention(rope(q, pos, theta), rope(k, pos, theta), v)
        x = x + proj(o.reshape(s, heads * hd), a["wo"]["w"], bits)
        f = p["ffn"]
        h = rmsnorm(x, p["ln2"]["scale"], eps)
        g = proj(h, f["gate"]["w"], bits)
        u = proj(h, f["up"]["w"], bits)
        return x + proj(jax.nn.silu(g) * u, f["down"]["w"], bits), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.pad(x, ((0, n_out), (0, 0))), n_prompt - 1, n_out, 0)
    h = rmsnorm(rows, params["final_norm"]["scale"], eps)
    w = (params["embed"]["table"].T if tied else params["unembed"]["w"])
    logits = jnp.matmul(h, w.astype(jnp.float32), precision=HI)
    chosen = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - chosen, jnp.argmax(logits, axis=1)


def model_key(m: dict) -> tuple:
    """The static shape tuple :func:`token_gaps` takes, from a configuration
    file's ``model`` section."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (h, m["num_key_value_heads"], m.get("head_dim") or d // h,
            float(m["rope_theta"]), float(m["rms_norm_eps"]),
            bool(m["tie_word_embeddings"]))
