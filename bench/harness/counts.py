"""Work the model needs, counted from configuration shapes alone.

Operations are ``2*M*K*N`` per projection over the useful rows only (real
prompt tokens, live decode slots) and causal attention over the rows a token
really attends; bytes are the weights a step must read plus the live K/V
rows. The counts do not depend on which GEMM algorithm or kernel ran, so a
kernel that needs fewer multipliers, or a later kernel swap, cannot raise the
work it is credited with.
"""
from __future__ import annotations

import dataclasses
import re

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1,
                "pred": 1, "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


@dataclasses.dataclass(frozen=True)
class ModelShapes:
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
            proj = (self.proj_params
                    + 16 * self.layers * self.layer_proj_channels)
        else:
            proj = 2 * self.proj_params
        return proj + 2 * self.unembed_params

    def decode_bytes(self, tier: str, ctx_rows: int, new_rows: int) -> int:
        """One decode dispatch: the weights once, ``ctx_rows`` live K/V rows
        read over all slots, ``new_rows`` rows written."""
        return (self.weight_bytes(tier)
                + (ctx_rows + new_rows) * self.kv_bytes_per_token)


# -- kernels, from the operand shapes the trace prints ------------------------

_SHAPE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def hlo_shapes(text: str):
    """[(dtype, dims)] of every array shape in an HLO instruction's text."""
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(text)]


def flash_cost(op_text: str):
    """(operations, bytes) of one causal flash-attention forward call, from
    its custom-call text: outputs (o (BH,Sq,dv), lse (BH,Sq,1)), operands
    (window, q (BH,Sq,d), k (BH,Sk,d), v (BH,Sk,dv)). Returns None when the
    text does not have that form."""
    call = op_text.split("custom-call(", 1)
    if len(call) != 2:
        return None
    outs = hlo_shapes(call[0].split("=", 1)[-1])
    ins = hlo_shapes(call[1].split("), custom_call_target", 1)[0])
    if len(outs) != 2 or len(ins) != 4:
        return None
    (qt, q), (kt, k), (vt, v) = ins[1:]
    (ot, o), (lt, lse) = outs
    if not (len(q) == len(k) == len(v) == 3):
        return None
    bh, sq, d = q
    sk, dv = k[1], v[2]
    m = min(sq, sk)                 # causal pairs: sum of min(i + 1, sk)
    pairs = m * (m + 1) // 2 + (sq - m) * sk
    ops = 2 * bh * pairs * (d + dv)
    nbytes = 0
    for dt, dims in ((qt, q), (kt, k), (vt, v), (ot, o), (lt, lse)):
        size = 1
        for x in dims:
            size *= x
        nbytes += size * _DTYPE_BYTES.get(dt, 4)
    return ops, nbytes
