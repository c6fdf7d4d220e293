"""Model step: useful operations of the traced prefill dispatches (real
prompt tokens only) over the device time of the prefill program times the
cell's chips' peak for the tier's arithmetic. Each chip's execution of a
dispatch is credited the dispatch's operations over all the chips."""
from __future__ import annotations

from harness.readers import PREFILL_PROGRAM, dispatches, share


def read(rec):
    ops = secs = 0.0
    for s, pf in dispatches(rec, PREFILL_PROGRAM):
        ops += pf["flops"]
        secs += s
    return share(ops, secs * rec["chips"]
                 * rec["peaks"].compute(rec["tier"]))
