"""Batcher: prompt tokens over the rows the bucketed prefill computed
(slots x bucket per dispatch), from the server's ``prefill`` spans."""
from __future__ import annotations

from harness.readers import prefill_useful_share as read  # noqa: F401
