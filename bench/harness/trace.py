"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

On a TPU the trace holds, per chip, a ``/device:TPU:<i>`` plane whose
``XLA Modules`` line has one event per program execution (named
``jit_<function>(<fingerprint>)``) and whose ``XLA Ops`` line has one event
per executed HLO instruction, named by the instruction's text (so a
custom call's operand shapes can be read from it). The host's
``/host:CPU`` plane holds the harness's ``bench.*`` annotations. Event times
are nanoseconds on one clock for all planes.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_COLLECTIVE_OPS = (r"(all-reduce|all-gather|reduce-scatter|"
                   r"collective-permute|all-to-all)(-start|-done)?")
# by the instruction's name, or by its opcode where the name is another
COLLECTIVE = re.compile(r"^%" + _COLLECTIVE_OPS
                        + r"|^%\S+ = .+? " + _COLLECTIVE_OPS + r"\(")
WRAPPER = re.compile(r"^%(while|conditional|call)[.\s]")


@dataclasses.dataclass
class Interval:
    name: str
    t0: float          # seconds
    t1: float


@dataclasses.dataclass
class Device:
    """One chip's events."""
    modules: List[Interval]
    ops: List[Interval]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, Device]
    host: List[Interval]          # bench.* annotations, in time order

    def __post_init__(self):
        self._starts = [h.t0 for h in self.host]

    @property
    def window(self) -> Tuple[float, float]:
        """From the first harness annotation to the end of the last."""
        return (min(a.t0 for a in self.host), max(a.t1 for a in self.host))

    def overlapping(self, a: float, b: float, prefix: str = "bench."):
        """(overlap seconds, annotation) of the annotations that overlap
        [a, b]; the harness's annotations never nest."""
        i = bisect.bisect_left(self._starts, b) - 1
        while i >= 0 and self.host[i].t1 > a:
            h = self.host[i]
            ov = min(b, h.t1) - max(a, h.t0)
            if ov > 0 and h.name.startswith(prefix):
                yield ov, h
            i -= 1

    def label(self, a: float, b: float) -> str:
        """The annotation overlapping [a, b] most (``bench.step:12`` reads
        ``bench.step``), or ``none``."""
        best = max(self.overlapping(a, b), default=None, key=lambda o: o[0])
        return "none" if best is None else best[1].name.split(":", 1)[0]

    def step_of(self, a: float, b: float) -> Optional[int]:
        """k of the ``bench.step:k`` annotation overlapping [a, b] most."""
        best = max(self.overlapping(a, b, "bench.step:"), default=None,
                   key=lambda o: o[0])
        return None if best is None else int(best[1].name.split(":", 1)[1])


def load(path) -> Trace:
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(str(path))
    devices: Dict[str, Device] = {}
    host: List[Interval] = []
    for plane in prof.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(modules=[], ops=[])
            for line in plane.lines:
                dest = {"XLA Modules": dev.modules,
                        "XLA Ops": dev.ops}.get(line.name)
                if dest is not None:
                    dest.extend(Interval(e.name, e.start_ns * 1e-9,
                                         e.end_ns * 1e-9)
                                for e in line.events)
            devices[plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Interval(e.name, e.start_ns * 1e-9,
                                     e.end_ns * 1e-9)
                            for e in line.events
                            if e.name.startswith("bench."))
    host.sort(key=lambda a: a.t0)
    return Trace(devices=devices, host=host)


def union(intervals: List[Interval], lo: float, hi: float) -> List[tuple]:
    """Merged (t0, t1) of ``intervals`` clipped to [lo, hi]."""
    spans = sorted((max(i.t0, lo), min(i.t1, hi)) for i in intervals
                   if i.t1 > lo and i.t0 < hi)
    merged: List[list] = []
    for a, b in spans:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [tuple(m) for m in merged]


def busy_seconds(dev: Device, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(dev.ops, lo, hi))


def idle_gaps(tr: Trace, dev: Device, lo: float, hi: float) -> List[tuple]:
    """(label, seconds) of every gap in the device's busy time within
    [lo, hi], labelled by :meth:`Trace.label`."""
    busy = union(dev.ops, lo, hi)
    edges = [lo] + [t for ab in busy for t in ab] + [hi]
    return [(tr.label(a, b), b - a)
            for a, b in zip(edges[0::2], edges[1::2]) if b > a]


_OP = re.compile(r"^(%\S+) = (.+?) ([a-z][\w-]*)\(")


def op_key(name: str) -> str:
    """An op's instruction name, opcode and result shape, without
    layouts."""
    m = _OP.match(name)
    if m is None:
        return name[:120]
    return re.sub(r"\{[^}]*\}", "", f"{m[1]} {m[3]} {m[2]}")[:120]


def top_ops(dev: Device, lo: float, hi: float, n: int = 10) -> List[list]:
    tot: Dict[str, float] = collections.defaultdict(float)
    for o in dev.ops:
        if o.t0 >= lo and o.t1 <= hi and not WRAPPER.match(o.name):
            tot[op_key(o.name)] += o.t1 - o.t0
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def is_collective(name: str) -> bool:
    """Whether an op is a collective: a synchronous one, or either half of
    an async ``<op>-start``/``<op>-done`` pair."""
    return COLLECTIVE.match(name) is not None


def collective_seconds(dev: Device, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which a collective op ran on the device."""
    return sum(b - a for a, b in union(
        [o for o in dev.ops if is_collective(o.name)], lo, hi))


def programs_by_step(tr: Trace, dev: Device, program: str
                     ) -> Dict[int, List[Interval]]:
    """Executions of the program whose module name holds ``program``,
    grouped by the harness step they ran in, in time order."""
    out: Dict[int, List[Interval]] = collections.defaultdict(list)
    for m in sorted(dev.modules, key=lambda m: m.t0):
        if program in m.name:
            k = tr.step_of(m.t0, m.t1)
            if k is not None:
                out[k].append(m)
    return out
