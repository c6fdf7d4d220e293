"""Cells on more than one chip.

A four-chip cell at test size runs on four host CPU devices, in a process of
its own (``mesh_cell.py``; the device count is fixed when JAX starts): its
weights are made with the program's ``param_specs`` shardings, its served
tokens pass the reference check within the test-size limits, and the
control fails them, as does each fault planted under a whole run. On synthetic records, the readers that divide by the
chips' peak read four chips each busy for t as one chip busy for 4t, so no
share passes 100% in a four-chip cell; ``collective_share`` reads known
collective and busy intervals.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, tiny_cell
from harness import peaks, readers, serving, spec
from harness import trace as T

C4 = "deepseek-coder-33b-l31.tp4.float"


def metric(name):
    from run import reader
    return reader(name)


@pytest.fixture(scope="module")
def four_chip_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    proc = subprocess.run([sys.executable, str(BENCH / "tests" /
                                               "mesh_cell.py")],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_weights_made_with_param_specs(four_chip_run):
    w = four_chip_run["weights"]
    assert four_chip_run["device_count"] == 4
    assert w["param_specs"] and w["split_leaves"] > 0


def test_four_chip_cell_is_correct(four_chip_run):
    out = four_chip_run
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["max_gap"]["value"] <= out["limits"]["max_gap"]


def test_control_fails_the_four_chip_cell(four_chip_run):
    ctl, lim = four_chip_run["control"], four_chip_run["limits"]
    sound = four_chip_run["sound"]
    assert sound["max_gap"] <= lim["max_gap"] \
        and sound["mean_gap"] <= lim["mean_gap"]
    assert ctl["max_gap"] > lim["max_gap"] or ctl["mean_gap"] > lim["mean_gap"]


@pytest.mark.parametrize("fault", ["altered_token", "state_unchanged",
                                   "half_batch", "no_exchange"])
def test_broken_four_chip_path_is_not_correct(four_chip_run, fault):
    """Each fault the cell can have, planted under a whole run: a token
    altered where it is produced, a step that leaves its state unchanged,
    half of an admitted batch left out, the exchange between chips left
    out."""
    out = four_chip_run["faults"][fault]
    assert not out["correct"], out["checks"]


def decode_record(chips: int, busy: float) -> dict:
    """One decode step at the TP=4 configuration's shapes, traced on
    ``chips`` devices that each ran the decode program for ``busy``
    seconds."""
    m = json.loads((BENCH / "configs" / f"{C4}.json").read_text())["model"]
    shapes = spec.family("llama").ModelShapes.from_model(m)
    st = serving.Step(index=0, t0=0.0, t1=1.0, tokens=4, decode_tokens=4,
                      decode_ctx_rows=4 * 2000,
                      decode_flops=4 * shapes.decode_flops(2000))
    devices = {
        f"/device:TPU:{i}": T.Device(
            modules=[T.Interval("jit__decode_impl(7)", 0.1, 0.1 + busy)],
            ops=[T.Interval("%fusion.1 = bf16[8] fusion(bf16[8] %a)", 0.1,
                            0.1 + busy)])
        for i in range(chips)}
    tr = T.Trace(devices=devices, host=[T.Interval("bench.step:0", 0.0, 1.0)])
    return {"tier": "float", "slots": 4, "chips": chips, "shapes": shapes,
            "peaks": peaks.peaks("TPU v5 lite"), "seconds": 1.0,
            "steps": [st], "all_steps": {0: st}, "trace": tr}


def test_four_chips_read_as_one_chip_four_times_as_long():
    four = decode_record(4, 0.0)
    least = readers.decode_least_seconds(four, four["steps"][0])
    t = 2.0 * least
    four = decode_record(4, t)
    one = decode_record(1, 4 * t)
    roof = metric("decode_roofline.backlog")
    assert roof(four) == pytest.approx(50.0)
    assert roof(one) == pytest.approx(roof(four))
    # at the least time the share is whole, and no more
    assert roof(decode_record(4, least)) == pytest.approx(100.0)

    mfu = metric("mfu")
    pk = four["peaks"].compute("float")
    whole = four["steps"][0].decode_flops / (4 * pk)  # 4 chips at peak
    four["seconds"], one["seconds"] = 2 * whole, 8 * whole
    assert mfu(four) == pytest.approx(50.0)
    assert mfu(one) == pytest.approx(mfu(four))


def test_collective_share_on_synthetic_trace():
    op = "%{0} = bf16[8] {1}(bf16[8] %x)".format
    dev0 = T.Device(modules=[], ops=[
        T.Interval(op("fusion.1", "fusion"), 0.0, 1.0),
        T.Interval(op("all-reduce-start.2", "all-reduce-start"), 1.0, 1.2),
        T.Interval(op("all-reduce-done.2", "all-reduce-done"), 1.5, 2.0),
        T.Interval(op("fusion.3", "fusion"), 2.0, 3.0)])
    dev1 = T.Device(modules=[], ops=[
        T.Interval(op("fusion.1", "fusion"), 0.0, 2.0),
        T.Interval(op("ag.4", "all-gather"), 2.0, 2.5),
        T.Interval(op("fusion.5", "fusion"), 2.25, 2.75)])
    tr = T.Trace(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1},
                 host=[T.Interval("bench.step:0", 0.0, 4.0)])
    # busy 2.7 s and 2.75 s; collectives 0.7 s and 0.5 s
    assert metric("collective_share")({"trace": tr}) == pytest.approx(
        100.0 * 1.2 / 5.45)
    one = T.Trace(devices={"/device:TPU:0": T.Device(modules=[], ops=[
        T.Interval(op("fusion.1", "fusion"), 0.0, 1.0)])},
        host=[T.Interval("bench.step:0", 0.0, 4.0)])
    assert metric("collective_share")({"trace": one}) is None
    assert metric("collective_share")({"trace": None}) is None


def test_one_chip_cell_has_no_mesh():
    cell = tiny_cell("minicpm-2b.float", "chat")
    assert serving.Session(cell).mesh is None
