"""Trace reduction, checked on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_trace.py``: a two-layer model
at small widths serving six requests through ``BatchServer``)."""
from __future__ import annotations

import pathlib

import pytest

from harness import readers
from harness import trace as T
from harness.counts import flash_cost

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.load(DATA)


def test_planes_and_annotations(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    names = [h.name for h in tr.host]
    assert names.count("bench.submit") == 6
    assert [n for n in names if n.startswith("bench.step:")] == [
        f"bench.step:{k}" for k in range(10)]


def test_busy_union_matches_program_time(tr):
    """The union of op intervals and the sum of program executions are two
    readings of the same busy time."""
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window
    busy = T.busy_seconds(dev, lo, hi)
    programs = sum(m.t1 - m.t0 for m in dev.modules)
    assert busy == pytest.approx(programs, rel=0.05)
    assert 0 < busy < hi - lo


def test_idle_gaps_cover_the_rest_and_are_labelled(tr):
    dev = tr.devices["/device:TPU:0"]
    lo, hi = tr.window
    gaps = T.idle_gaps(tr, dev, lo, hi)
    assert sum(s for _, s in gaps) == pytest.approx(
        (hi - lo) - T.busy_seconds(dev, lo, hi), rel=1e-9)
    labels = {lab for lab, _ in gaps}
    assert labels <= {"bench.submit", "bench.step", "none"}
    longest = max(gaps, key=lambda g: g[1])
    assert longest[0] == "bench.step"       # host work between dispatches


def test_programs_grouped_by_step(tr):
    dev = tr.devices["/device:TPU:0"]
    prefill = T.programs_by_step(tr, dev, readers.PREFILL_PROGRAM)
    decode = T.programs_by_step(tr, dev, readers.DECODE_PROGRAM)
    assert {k: len(v) for k, v in prefill.items()} == {0: 2, 5: 2}
    assert sorted(decode) == list(range(10))
    assert all(len(v) == 1 for v in decode.values())


def test_one_chip_has_no_collectives(tr):
    dev = tr.devices["/device:TPU:0"]
    assert T.collective_seconds(dev, *tr.window) == 0.0


def test_collectives_and_union_on_synthetic_events():
    ops = [T.Interval("%fusion.1 = f32[8] fusion(f32[8] %a)", 0.0, 1.0),
           T.Interval("%all-reduce.3 = f32[8] all-reduce(f32[8] %b)", 0.5, 2.0),
           T.Interval("%all-reduce-start.1 = f32[8] all-reduce-start(f32[8] %c)",
                      3.0, 3.5),
           T.Interval("%fusion.2 = f32[8] fusion(f32[8] %d)", 4.0, 5.0)]
    dev = T.Device(modules=[], ops=ops)
    assert T.busy_seconds(dev, 0.0, 6.0) == pytest.approx(3.5)
    assert T.collective_seconds(dev, 0.0, 6.0) == pytest.approx(2.0)
    assert T.busy_seconds(dev, 1.5, 4.5) == pytest.approx(1.5)


def test_flash_calls_read_from_their_operand_shapes(tr):
    dev = tr.devices["/device:TPU:0"]
    flash = [o for o in dev.ops if o.name.startswith(readers.FLASH_KERNEL)]
    assert len(flash) == 8                  # 2 layers x 4 prefill dispatches
    costs = {flash_cost(o.name) for o in flash}
    assert None not in costs
    # bucket 64: 8 rows of (batch x heads), causal pairs 64*65/2, d = 128
    assert (2 * 8 * (64 * 65 // 2) * 256, 4 * 8 * 64 * 128 * 2
            + 8 * 64 * 4) in costs


def test_top_ops_leave_out_loop_wrappers(tr):
    dev = tr.devices["/device:TPU:0"]
    top = T.top_ops(dev, *tr.window)
    assert len(top) == 10
    assert not any(name.startswith("%while") for name, _ in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
