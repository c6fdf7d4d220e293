"""Kernel, backlog cells: as ``flash_roofline``."""
from __future__ import annotations

from harness.readers import flash_roofline as read  # noqa: F401
