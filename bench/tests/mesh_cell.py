"""A four-chip cell at test size on four host CPU devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/mesh_cell.py

The device count has to be set before JAX starts, so ``test_mesh.py`` runs
this in a process of its own. It cuts the TP=4 configuration to test size
(its heads and K/V heads still divide the four chips) and prints one JSON
line: the result of a whole ``run.measure`` on the four devices, whether the
weights were made with the program's ``param_specs`` shardings, the widest
and mean gap of a sound window's tokens and of the control's at the same
positions, and ``correct`` of a whole run with each fault this cell can
have planted underneath: the faults of ``test_correctness.py`` and the
exchange between chips left out.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

TESTS = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(TESTS))

from conftest import tiny_cell  # noqa: E402  (puts bench/ and src/ on the path)

CONFIG = "deepseek-coder-33b-l31.tp4.float"
SEED = 2 ** 40 + 11
CHIPS = 4
# widths a four-way "model" axis divides: 8 heads of 32, 4 K/V heads. The
# test-size limits hold here too (CPU readings, seed 2**40+11, four runs):
# sound runs read a widest gap of 0.0010-0.030 and a mean of at most
# 0.0003; the control read 1.9-2.1 widest and 0.033-0.12 mean.
MESH_MODEL = {"hidden_size": 256, "num_attention_heads": 8,
              "num_key_value_heads": 4}


def mesh_cell():
    cell = tiny_cell(CONFIG, "code-backlog")
    cell.chips = CHIPS
    cell.config["model"].update(MESH_MODEL)
    return cell


def sharded_as_specified(sess) -> dict:
    """Whether every weight leaf carries the sharding ``param_specs`` gives
    it, and how many leaves are split over the four devices."""
    import jax
    from repro.dist import sharding
    want = sharding.to_named(sharding.param_specs(sess.params, sess.mesh),
                             sess.mesh)
    leaves = jax.tree.leaves(sess.params)
    same = all(x.sharding.is_equivalent_to(w, x.ndim) for x, w in
               zip(leaves, jax.tree.leaves(want)))
    split = sum(1 for x in leaves if not x.sharding.is_fully_replicated)
    return {"param_specs": same, "split_leaves": split,
            "leaves": len(leaves)}


def _no_exchange(monkeypatch):
    """The MLP's row-parallel down projection keeps each chip's partial
    product: the all-reduce that sums them is left out."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.dist.context import get_mesh
    from repro.models import layers as L
    orig = L.mlp

    def mlp(x, p, act="silu"):
        mesh = get_mesh()
        if mesh is None:
            return orig(x, p, act)
        h = L.act_fn(act)(L.dense(x, p["gate"])) * L.dense(x, p["up"])
        lead = (None,) * (h.ndim - 1)
        return jax.shard_map(
            lambda a, w: a @ w, mesh=mesh,
            in_specs=(P(*lead, "model"), P("model", None)),
            out_specs=P(), check_vma=False)(h, p["down"]["w"])
    monkeypatch.setattr(L, "mlp", mlp)


def faults():
    from test_correctness import _altered_token, _half_batch, _state_unchanged
    return {f.__name__.lstrip("_"): f for f in (
        _altered_token, _state_unchanged, _half_batch, _no_exchange)}


def main() -> int:
    import jax
    import pytest
    from control import control_gaps
    from harness import check, serving, traffic
    from run import REFERENCE_BITS, measure
    devs = jax.devices()[:CHIPS]
    if len(devs) < CHIPS:
        print(f"needs {CHIPS} devices, has {len(devs)}", file=sys.stderr)
        return 1
    cell = mesh_cell()
    out = measure(cell, SEED, 1.5, False, devs, time.perf_counter())
    sess = serving.Session(cell, devs)
    sess.build_server()
    sess.make_weights(SEED)
    weights = sharded_as_specified(sess)
    sess.warm_up()
    run = sess.run(SEED, 1.5)
    sess.free_server()
    picked = check.sample(run["records"], SEED)
    max_out = int(max(traffic.output_lengths(cell.traffic)))
    sound = check.gaps(cell.family, sess.params, picked, cell.config["model"],
                       sess.max_len, max_out, REFERENCE_BITS[cell.tier])
    ctl = control_gaps(sess, picked, max_out)
    sess = None
    broken = {}
    for name, fault in faults().items():
        with pytest.MonkeyPatch.context() as mp:
            fault(mp)
            bad = measure(cell, SEED, 1.5, False, devs, time.perf_counter())
        broken[name] = {"correct": bad["correct"], "checks": bad["checks"]}
    print(json.dumps({
        "correct": out["correct"], "checks": out["checks"],
        "attempted": out["attempted"], "failed": out["failed"],
        "device_count": out["device"]["count"], "weights": weights,
        "limits": cell.config["limits"],
        "sound": {"max_gap": float(sound.max()),
                  "mean_gap": float(sound.mean()), "tokens": int(sound.size)},
        "control": {"max_gap": float(ctl.max()),
                    "mean_gap": float(ctl.mean())},
        "faults": broken}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
