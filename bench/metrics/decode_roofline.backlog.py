"""Model step, backlog cells: as ``decode_roofline``."""
from __future__ import annotations

from harness.readers import decode_roofline as read  # noqa: F401
