"""Work counted from shapes alone.

A model's step is counted by its family module's ``ModelShapes``
(``bench/harness/families/<family>.py``): ``2*M*K*N`` per projection over
the useful rows only (real prompt tokens, live decode slots) and causal
attention over the rows a token really attends; bytes are the weights a step
must read plus the live K/V rows. A kernel call is counted here, from the
operand shapes the trace prints. Neither count depends on which GEMM
algorithm or kernel ran, so a kernel that needs fewer multipliers, or a
later kernel swap, cannot raise the work it is credited with.
"""
from __future__ import annotations

import re

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1, "u8": 1,
                "pred": 1, "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


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
