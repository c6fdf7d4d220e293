"""Roofline-term extraction from compiled dry-run artifacts.

compute/memory terms come from compiled.cost_analysis(); the collective term
is parsed out of the post-SPMD optimized HLO (collective ops do not appear in
cost_analysis). Bytes-on-wire model per op (ring algorithms, group size N):

    all-gather:          out_bytes * (N-1)/N        (out is the gathered buf)
    reduce-scatter:      out_bytes * (N-1)          (operand = out * N)
    all-reduce:          2 * bytes * (N-1)/N        (RS + AG phases)
    all-to-all:          bytes * (N-1)/N
    collective-permute:  bytes
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

from repro.core import analytical

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(?)((?:[a-z0-9]+\[[0-9,]*\][^\s]*(?:,\s*)?)+)\)?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.M)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9,\s]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))   # [ngroups, group_size]
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, float]

    @property
    def total_bytes(self) -> float:
        return sum(self.bytes_by_kind.values())


_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(r"while\(.*?\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo_text.splitlines():
        m = _COMP_HDR_RE.match(line.strip()) if "{" in line and "->" in line else None
        if m and cur is None:
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


def _line_collective(line: str):
    m = _COLL_RE.search(line)
    if not m or "-done(" in line:
        return None
    type_str, kind = m.group(1), m.group(2)
    size = _tensor_bytes(type_str)
    n = _group_size(line)
    if kind == "all-gather":
        wire = size * (n - 1) / max(n, 1)
    elif kind == "reduce-scatter":
        wire = size * (n - 1)
    elif kind == "all-reduce":
        wire = 2 * size * (n - 1) / max(n, 1)
    elif kind == "all-to-all":
        wire = size * (n - 1) / max(n, 1)
    else:
        wire = float(size)
    return kind, wire


def _trip_count(cond_lines: List[str]) -> int:
    """Loop bound heuristic: the largest integer constant in the while cond."""
    best = 1
    for line in cond_lines:
        for c in _CONST_RE.findall(line):
            best = max(best, int(c))
    return best


def collective_bytes(hlo_text: str) -> CollectiveStats:
    """Modeled bytes-on-wire per collective kind, WHILE-LOOP AWARE: collectives
    inside scan/while bodies are multiplied by the loop trip count (XLA's own
    cost analysis counts them once, which silently hides per-layer traffic)."""
    comps = _split_computations(hlo_text)
    memo: Dict[str, Tuple[Dict[str, int], Dict[str, float]]] = {}

    def walk(name: str, stack=()) -> Tuple[Dict[str, int], Dict[str, float]]:
        if name in memo:
            return memo[name]
        if name in stack or name not in comps:
            return {}, {}
        counts: Dict[str, int] = {}
        by_kind: Dict[str, float] = {}
        for line in comps[name]:
            hit = _line_collective(line)
            if hit:
                kind, wire = hit
                counts[kind] = counts.get(kind, 0) + 1
                by_kind[kind] = by_kind.get(kind, 0.0) + wire
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                trips = _trip_count(comps.get(cond, []))
                sub_counts, sub_bytes = walk(body, stack + (name,))
                for k, v in sub_counts.items():
                    counts[k] = counts.get(k, 0) + v * trips
                for k, v in sub_bytes.items():
                    by_kind[k] = by_kind.get(k, 0.0) + v * trips
        memo[name] = (counts, by_kind)
        return memo[name]

    counts, by_kind = walk("__entry__")
    if not counts and not by_kind:
        # fallback: flat scan over all lines (entry parse failed)
        for line in hlo_text.splitlines():
            hit = _line_collective(line)
            if hit:
                kind, wire = hit
                counts[kind] = counts.get(kind, 0) + 1
                by_kind[kind] = by_kind.get(kind, 0.0) + wire
    return CollectiveStats(counts=counts, bytes_by_kind=by_kind)


def remat_duplication(hlo_text: str) -> float:
    """Heuristic recompute indicator: ratio of dot/convolution ops to unique
    fusion-site names (duplicate op base-names signal remat-inserted clones)."""
    names = re.findall(r"%([a-z_\.\-0-9]+) = [a-z0-9]+\[", hlo_text)
    base = [n.rsplit(".", 1)[0] for n in names]
    if not base:
        return 0.0
    return 1.0 - len(set(base)) / len(base)


def roofline_report(flops: float, hlo_bytes: float, coll: CollectiveStats,
                    chips: int, *,
                    device_kind: str = analytical.DRYRUN_DEVICE_KIND,
                    model_flops: Optional[float] = None) -> dict:
    """Roofline terms against the published peaks of ``device_kind``
    (core.analytical.CHIP_PEAKS; an unknown kind raises)."""
    peaks = analytical.chip_peaks(device_kind)
    compute_s = flops / (chips * peaks.bf16_flops)
    memory_s = hlo_bytes / (chips * peaks.hbm_bw)
    collective_s = coll.total_bytes / (chips * peaks.ici_bw)
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    out = dict(terms)
    out.update(
        bottleneck=dominant,
        step_time_lower_bound_s=bound,
        # fraction of the step-time bound that is *useful compute*: 1.0 means
        # perfectly compute-bound (the roofline optimum for a given algorithm)
        roofline_fraction=(compute_s / bound) if bound else 0.0,
        collective_counts=coll.counts,
        collective_bytes=coll.total_bytes,
    )
    if model_flops:
        out["model_flops"] = model_flops
        out["useful_flops_ratio"] = model_flops / flops if flops else 0.0
    return out
