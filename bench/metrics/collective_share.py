"""Dist: time in collective operations over busy time, on every chip of the
cell together, in percent. Collectives are the all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all ops, each half of an
async start/done pair counted as the trace prints it
(``harness.trace.is_collective``). None where the trace holds no
collective."""
from __future__ import annotations

from harness import trace as tr_mod
from harness.readers import share


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    lo, hi = tr.window
    return share(sum(tr_mod.collective_seconds(d, lo, hi)
                     for d in tr.devices.values()),
                 sum(tr_mod.busy_seconds(d, lo, hi)
                     for d in tr.devices.values()))
