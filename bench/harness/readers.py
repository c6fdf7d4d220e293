"""What the metric readers under ``bench/metrics`` share.

A reader is ``read(rec) -> float | None``. ``rec`` is the run's record:

  ``tier``, ``slots``, ``chips``; ``shapes``
  (:class:`harness.counts.ModelShapes`); ``peaks``
  (:class:`harness.peaks.Peaks`); ``setup_s``; ``t0``/``t_stop``/``seconds``
  of the measured window; ``records`` (every request the window attempted,
  :class:`harness.serving.Record`); ``steps`` (the ``step`` calls that began
  and ended inside the window, :class:`harness.serving.Step`); ``all_steps``
  (by index, the fill's and the drain's too); ``trace`` (a :class:`harness.trace.Trace`, or None
  without ``--trace 1``).

A reader that finds nothing to read returns None and the metric is left out
of the result line; it never returns 0 for a share of a roofline or a peak.
"""
from __future__ import annotations

from typing import Iterator, Optional, Tuple

import numpy as np

from harness import trace as tr_mod

PREFILL_PROGRAM = "_prefill_bucket_impl"
DECODE_PROGRAM = "_decode_impl"
FLASH_KERNEL = "%_flash_fwd"


def percentile(values, q: float) -> Optional[float]:
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def ttfts_ms(rec):
    """Due time to first token of every request whose first token landed
    in the window."""
    return [1e3 * (r.t_first - r.due) for r in rec["records"]
            if r.t_first and in_window(rec, r.t_first)]


def tpots_ms(rec):
    """Time per output token after the first, of every request completed
    in the window."""
    return [1e3 * (r.t_done - r.t_first) / (len(r.out) - 1)
            for r in rec["records"]
            if r.t_done and in_window(rec, r.t_done) and len(r.out) > 1]


def in_window(rec, t: float) -> bool:
    return rec["t0"] < t <= rec["t_stop"]


def share(num: float, den: float) -> Optional[float]:
    """``num / den`` in percent, or None where there was nothing to
    measure."""
    return 100.0 * num / den if den > 0 and num > 0 else None


def dispatches(rec, program: str) -> Iterator[Tuple[float, object]]:
    """(device seconds, what the harness knows of it) of every traced
    execution of ``program`` that can be matched to its dispatch, on every
    chip: a prefill execution to the entry of ``Step.prefills`` in the same
    place of the same step, a decode execution to its step. Steps the trace
    holds only a part of are skipped."""
    tr = rec["trace"]
    if tr is None:
        return
    for dev in tr.devices.values():
        for k, mods in tr_mod.programs_by_step(tr, dev, program).items():
            st = rec["all_steps"].get(k)
            if st is None:
                continue
            if program == PREFILL_PROGRAM:
                if len(mods) == len(st.prefills):
                    for m, pf in zip(mods, st.prefills):
                        yield m.t1 - m.t0, pf
            elif len(mods) == 1 and st.decode_tokens:
                yield mods[0].t1 - mods[0].t0, st


def decode_least_seconds(rec, st) -> float:
    """The least time a decode dispatch could take on the cell's chips:
    its operations at the tier's peak, or its weight and live K/V bytes at
    the memory bandwidth, whichever is larger, of all the chips together."""
    n, pk, shapes = rec["chips"], rec["peaks"], rec["shapes"]
    ops = st.decode_flops / (n * pk.compute(rec["tier"]))
    nbytes = shapes.decode_bytes(rec["tier"], st.decode_ctx_rows,
                                 st.decode_tokens)
    return max(ops, nbytes / (n * pk.hbm_bw))


def decode_roofline(rec) -> Optional[float]:
    """Least time over device time, summed over every chip's execution of
    each dispatch: each chip's is held to the least time of all the chips
    together, so four chips busy for t read as one chip busy for 4t."""
    least = dev_s = 0.0
    for secs, st in dispatches(rec, DECODE_PROGRAM):
        least += decode_least_seconds(rec, st)
        dev_s += secs
    return share(least, dev_s)


def flash_roofline(rec) -> Optional[float]:
    """Least time of each traced flash-attention call, from its own operand
    shapes (causal operations, q/k/v/o/lse bytes), over its device time."""
    from harness.counts import flash_cost
    tr = rec["trace"]
    if tr is None:
        return None
    pk = rec["peaks"]
    least = dev_s = 0.0
    lo, hi = tr.window
    for dev in tr.devices.values():
        for o in dev.ops:
            if o.name.startswith(FLASH_KERNEL) and lo <= o.t0 and o.t1 <= hi:
                cost = flash_cost(o.name)
                if cost is None:
                    continue
                least += max(cost[0] / pk.bf16_flops, cost[1] / pk.hbm_bw)
                dev_s += o.t1 - o.t0
    return share(least, dev_s)


def prefill_useful_share(rec) -> Optional[float]:
    rows = padded = 0
    for st in rec["steps"]:
        for pf in st.prefills:
            rows += pf["rows"]
            padded += rec["slots"] * pf["bucket"]
    return share(rows, padded)


def window_flops(rec) -> float:
    return sum(st.decode_flops + sum(pf["flops"] for pf in st.prefills)
               for st in rec["steps"])
