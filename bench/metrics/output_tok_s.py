"""Output tokens per second: every token the server emitted in ``step``
calls that began and ended inside the window, over the window's seconds."""
from __future__ import annotations


def read(rec):
    return sum(st.tokens for st in rec["steps"]) / rec["seconds"]
