"""Device: share of the traced window in which no operation ran, averaged
over the cell's chips."""
from __future__ import annotations

from harness import trace as tr_mod


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr.devices:
        return None
    lo, hi = tr.window
    busy = sum(tr_mod.busy_seconds(d, lo, hi) for d in tr.devices.values())
    return 100.0 * (1.0 - busy / (len(tr.devices) * (hi - lo)))
