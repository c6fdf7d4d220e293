"""Median, over every request whose first token landed in the window, of
the time from its due time (its scheduled arrival) to that token on the
host."""
from __future__ import annotations

from harness.readers import percentile, ttfts_ms


def read(rec):
    return percentile(ttfts_ms(rec), 50)
