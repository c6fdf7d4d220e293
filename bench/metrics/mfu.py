"""Model step: useful operations of every token the window processed
(prompt tokens prefilled, tokens decoded) over the window's seconds times
the chips' peak for the tier's arithmetic."""
from __future__ import annotations

from harness.readers import share, window_flops


def read(rec):
    return share(window_flops(rec), rec["seconds"] * rec["chips"]
                 * rec["peaks"].compute(rec["tier"]))
