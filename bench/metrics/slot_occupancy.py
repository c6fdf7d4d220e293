"""Batcher: decoded tokens over the rows the decode dispatches computed
(dispatches x slots; the server decodes one token a slot a dispatch)."""
from __future__ import annotations

from harness.readers import share


def read(rec):
    steps = [st for st in rec["steps"] if st.decode_tokens]
    return share(sum(st.decode_tokens for st in steps),
                 len(steps) * rec["slots"])
