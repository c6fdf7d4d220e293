"""Load generator: 90th percentile of how late requests due in the window
were handed to ``submit``. The generator and the server share one thread,
so this is the wait behind the ``step`` call running at the due time."""
from __future__ import annotations

from harness.readers import in_window, percentile


def read(rec):
    return percentile((1e3 * (r.t_submit - r.due) for r in rec["records"]
                       if in_window(rec, r.due)), 90)
