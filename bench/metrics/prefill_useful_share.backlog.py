"""Batcher, backlog cells: as ``prefill_useful_share``."""
from __future__ import annotations

from harness.readers import prefill_useful_share as read  # noqa: F401
