"""90th percentile, over every request completed in the window, of
``(t_last - t_first) / (n_out - 1)``: its time per output token after the
first."""
from __future__ import annotations

from harness.readers import percentile, tpots_ms


def read(rec):
    return percentile(tpots_ms(rec), 90)
