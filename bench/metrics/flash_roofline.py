"""Kernel: the Pallas flash-attention prefill kernel's least time (causal
operations, q/k/v/o bytes, from each call's operand shapes in the trace)
over its device time."""
from __future__ import annotations

from harness.readers import flash_roofline as read  # noqa: F401
