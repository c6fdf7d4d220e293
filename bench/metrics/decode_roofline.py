"""Model step: least time of each traced decode dispatch (its operations
at peak, or the weights plus live K/V rows at the memory bandwidth) over
the decode program's device time."""
from __future__ import annotations

from harness.readers import decode_roofline as read  # noqa: F401
