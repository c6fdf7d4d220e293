"""One general generator for every traffic mix.

A mix file (``bench/traffic/<mix>.json``) gives the arrival process and the
length distributions; this module turns it and a seed into requests. Lengths
and gaps are drawn block-stratified: each block of ``block`` requests holds
the same ``block`` quantiles of each distribution, in an order the seed
chooses (prompt lengths, output lengths and gaps each on their own). The
order is stratified too: every ``SUB`` consecutive requests hold one
quantile from each ``SUB``-th of the distribution, so a window that ends
inside a block still holds about the block's mix. So every seed offers the
same set of lengths and arrivals in every block, in another order, and the
seed also draws the token ids.

Mix keys:
  ``arrival``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop, gaps
  exponential) or ``{"kind": "backlog"}`` (offline: the queue never empties);
  ``prompt``, ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal, clipped, in tokens;
  ``block``: requests per stratified block, a multiple of ``SUB``;
  ``trace_seconds``: length of the profiler trace a ``--trace 1`` run takes;
  ``source``, ``published``, ``reduced``, ``assumed``: the trace the mix
  follows, its published numbers, what was cut from them and why, and what
  the source does not give (read by people, not by the harness).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import statistics
from typing import Iterator, List

import numpy as np


SUB = 4     # requests per sub-block: one from each quarter of the block


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    prompt: np.ndarray          # (n,) int32 token ids
    max_new: int
    gap_s: float                # after the previous request (0 for backlog)


def _quantiles(block: int) -> List[float]:
    return [(i + 0.5) / block for i in range(block)]


def _lognormal(spec: dict, block: int) -> np.ndarray:
    z = [statistics.NormalDist().inv_cdf(u) for u in _quantiles(block)]
    x = [spec["median"] * math.exp(spec["sigma"] * zi) for zi in z]
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(arrival: dict, block: int) -> np.ndarray:
    if arrival["kind"] == "backlog":
        return np.zeros(block)
    if arrival["kind"] != "poisson":
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    rate = float(arrival["rate_per_s"])
    return np.array([-math.log(1.0 - u) / rate for u in _quantiles(block)])


def _order(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """A block's quantiles (ascending) in an order drawn from ``rng`` in
    which every ``SUB`` consecutive values hold one from each ``SUB``-th of
    the block."""
    parts = np.stack([rng.permutation(p) for p in values.reshape(SUB, -1)])
    return np.concatenate([rng.permutation(col) for col in parts.T])


def requests(mix: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """The endless request stream of ``mix`` for ``seed``."""
    block = int(mix["block"])
    prompts = _lognormal(mix["prompt"], block)
    outputs = _lognormal(mix["output"], block)
    gaps = _gaps(mix["arrival"], block)
    tokens = np.random.default_rng(seed)
    order = np.random.default_rng([seed, 2])
    i = 0
    for _ in itertools.count():
        p, o, g = (_order(x, order) for x in (prompts, outputs, gaps))
        for n, m, gap in zip(p, o, g):
            yield Planned(index=i, prompt=tokens.integers(
                0, vocab, int(n), dtype=np.int32),
                max_new=int(m), gap_s=float(gap))
            i += 1


def prompt_lengths(mix: dict) -> np.ndarray:
    """The prompt lengths of one block: every seed sends these, in its own
    order."""
    return _lognormal(mix["prompt"], int(mix["block"]))


def output_lengths(mix: dict) -> np.ndarray:
    """The output lengths of one block."""
    return _lognormal(mix["output"], int(mix["block"]))
