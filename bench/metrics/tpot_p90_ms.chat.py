"""90th percentile, over every request completed in the window, of
``(t_last - t_first) / (n_out - 1)``: its time per output token after the
first. Per layer: in the chat cell the window's p90 is its 4th largest of
about 36 TPOTs, set by which short answers share their decode with a long
admission prefill, and the seed orders that, so it spreads too widely from
seed to seed to hold a bound end to end."""
from __future__ import annotations

from harness.readers import percentile, tpots_ms


def read(rec):
    return percentile(tpots_ms(rec), 90)
