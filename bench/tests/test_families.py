"""Family modules, found by file name under ``bench/harness/families``.

The llama module holds what the harness computed before it was split into
families; the numbers below were read from that harness at the same sizes
and seeds (work counts at full and test size, the tiny cells' initial
weights and the reference's gaps and argmax on one sequence), so a module
that departs from it fails here. A family that is not there is named by the
file looked for, and one in a new file is found with no edit to the harness.
"""
from __future__ import annotations

import hashlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (BENCH, ROOT, TINY_MODEL, TINY_SERVING, TINY_TRAFFIC,
                      tiny_cell)
from harness import serving, spec

SEED = 2 ** 40 + 11
LLAMA = spec.family("llama")

# [param_count, prefill_flops(1000), decode_flops(1000), weight_bytes float,
#  weight_bytes int8, decode_bytes float (3000, 4), decode_bytes int8
#  (3000, 4), kv_bytes_per_token, vocab]
COUNTS = {
    "minicpm-2b.float/full": [2724880896, 5068812685824, 5818028544, 5449388544, 3022262784, 6556783104, 4129657344, 368640, 122753],
    "minicpm-2b.float/tiny": [361088, 1102467072, 1744896, 720896, 458752, 2258944, 1996800, 512, 512],
    "minicpm-2b.int8/full": [2724880896, 5068812685824, 5818028544, 5449388544, 3022262784, 6556783104, 4129657344, 368640, 122753],
    "minicpm-2b.int8/tiny": [361088, 1102467072, 1744896, 720896, 458752, 2258944, 1996800, 512, 512],
    "deepseek-coder-33b-l8.float/full": [4705082368, 8600342102016, 9176875008, 8947499008, 4712890368, 9045934080, 4811325440, 32768, 32256],
    "deepseek-coder-33b-l8.float/tiny": [426624, 1102467072, 1744896, 720896, 458752, 2258944, 1996800, 512, 512],
}
# the tiny cells' weights (sha256 of every leaf's bytes, in tree order) and
# the reference's gaps and argmax on one 64-token sequence, 12 served tokens
# from position 31, float and at 8 bits
REFERENCE = {
    "minicpm-2b.float": {
        "weights": "f272e5623242ba4fafe986cb1b792842c305d766dd13cf1e0b35d8e06aa7acbd",
        "gaps0": [0.9719688892364502, 0.5850316286087036, 0.9487518668174744, 0.7554393410682678, 0.529691219329834, 0.47974324226379395, 0.6400415897369385, 0.3703261911869049, 0.7867101430892944, 0.6978654265403748, 0.7901723384857178, 0.38633811473846436],
        "best0": [209, 245, 268, 119, 5, 255, 479, 400, 103, 18, 103, 92],
        "gaps8": [0.9823521375656128, 0.6258058547973633, 0.9681074619293213, 0.7519032955169678, 0.5340480804443359, 0.49227648973464966, 0.6502915620803833, 0.37344956398010254, 0.7695549726486206, 0.698341965675354, 0.7883985638618469, 0.37333375215530396],
        "best8": [209, 245, 245, 119, 5, 255, 245, 400, 103, 18, 103, 92],
    },
    "deepseek-coder-33b-l8.float": {
        "weights": "7c497b4379db0e5b48662b3d969db22c959d6bd7c4e729f8c5a896949e78eae0",
        "gaps0": [5.074918270111084, 1.7605881690979004, 3.0764548778533936, 3.3295836448669434, 3.282452344894409, 2.951819896697998, 5.163250923156738, 2.140172004699707, 3.621992826461792, 3.519766092300415, 4.207368850708008, 3.607140064239502],
        "best0": [112, 341, 49, 467, 49, 112, 211, 456, 489, 102, 153, 341],
        "gaps8": [5.026887893676758, 1.7874482870101929, 3.1254284381866455, 3.2953908443450928, 3.293006420135498, 2.914314031600952, 5.108922958374023, 2.15310001373291, 3.6508750915527344, 3.480433464050293, 4.220405578613281, 3.573173761367798],
        "best8": [112, 341, 49, 467, 49, 112, 211, 456, 489, 102, 153, 341],
    },
}


def shape_counts(s) -> list:
    return [s.param_count(), s.prefill_flops(1000), s.decode_flops(1000),
            s.weight_bytes("float"), s.weight_bytes("int8"),
            s.decode_bytes("float", 3000, 4), s.decode_bytes("int8", 3000, 4),
            s.kv_bytes_per_token, s.vocab]


@pytest.mark.parametrize("key", sorted(COUNTS))
def test_llama_counts_as_before(key):
    config, size = key.split("/")
    mix = "chat" if config.startswith("minicpm") else "code-backlog"
    model = (json.loads((BENCH / "configs" / f"{config}.json").read_text())
             ["model"] if size == "full"
             else tiny_cell(config, mix).config["model"])
    assert shape_counts(LLAMA.ModelShapes.from_model(model)) == COUNTS[key]


@pytest.mark.parametrize("config,mix", [("minicpm-2b.float", "chat"),
                                        ("deepseek-coder-33b-l8.float",
                                         "code-backlog")])
def test_llama_weights_and_reference_as_before(config, mix):
    cell = tiny_cell(config, mix)
    assert cell.family is LLAMA
    sess = serving.Session(cell)
    sess.make_weights(SEED)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(sess.params):
        h.update(np.asarray(leaf).tobytes())
    want = REFERENCE[config]
    assert h.hexdigest() == want["weights"]
    seq = np.random.default_rng(5).integers(0, 512, 64).astype(np.int32)
    for bits in (0, 8):
        gaps, best = LLAMA.token_gaps(
            sess.params, jnp.asarray(seq), jnp.asarray(31, jnp.int32),
            jnp.asarray(seq[30:42]), model=LLAMA.model_key(
                cell.config["model"]), bits=bits, n_out=12)
        np.testing.assert_array_equal(np.asarray(best), want[f"best{bits}"])
        np.testing.assert_allclose(np.asarray(gaps), want[f"gaps{bits}"],
                                   rtol=0, atol=1e-6)


def test_unknown_family_names_the_file(tmp_path):
    with pytest.raises(ValueError, match=r"families/mamba7\.py"):
        spec.family("mamba7", tmp_path)
    cell = tiny_cell("minicpm-2b.float", "chat")
    cell.config["family"] = "no-such-family"
    with pytest.raises(ValueError, match="no-such-family.py"):
        serving.Session(cell)


# A family of its own, in a checkout that has one more file: the Llama
# layer under another name, with an initializer for a leaf name the harness
# does not know (the program's tree has none, so it is never called).
TOY_FAMILY = """
import jax.numpy as jnp

from harness import spec

_llama = spec.family("llama")
model_config = _llama.model_config
ModelShapes = _llama.ModelShapes
token_gaps = _llama.token_gaps
model_key = _llama.model_key
INITIALIZERS = {"w_gate": lambda key, shape, dtype: jnp.zeros(shape, dtype)}
"""


def test_new_family_is_found_by_its_file(tmp_path):
    """A configuration of a new family is added with files alone: the
    family's module, its configuration and its ``BENCHMARK.json`` entries.
    The cell then runs through ``run.measure`` as any other."""
    from run import measure
    fam_dir = tmp_path / spec.FAMILIES
    fam_dir.mkdir(parents=True)
    (fam_dir / "toy.py").write_text(TOY_FAMILY)
    mix = json.loads((BENCH / "traffic" / "chat.json").read_text())
    mix.update(TINY_TRAFFIC)
    mix["arrival"]["rate_per_s"] = 40.0
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "traffic" / "chat.json").write_text(json.dumps(mix))
    cfg = json.loads((BENCH / "configs" / "minicpm-2b.float.json")
                     .read_text())
    cfg.update(name="toy", family="toy")
    cfg["model"].update(TINY_MODEL)
    cfg["serving"].update(TINY_SERVING)
    cfg["limits"] = {"max_gap": 0.1, "mean_gap": 0.005}
    (tmp_path / "toy.json").write_text(json.dumps(cfg))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "toy", "file": "toy.json"}]
    bench["workloads"] = [{"name": "toy.chat", "config": "toy",
                           "traffic": "chat", "chips": 1}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("toy.chat", tmp_path)
    fam = cell.family
    assert fam is not LLAMA and fam.__file__ == str(fam_dir / "toy.py")
    assert "w_gate" in fam.INITIALIZERS
    out = measure(cell, SEED, 1.0, False, jax.devices(), time.perf_counter())
    assert out["correct"], out["checks"]
    assert {"output_tok_s", "setup_s"} <= set(out["metrics"])


def test_family_initializers_reach_leaves_the_harness_does_not_know():
    """A leaf name beyond ``w``, ``table``, ``scale`` and ``b`` (the expert
    stacks of a MoE family, say) is made by its family's initializer, and
    is refused without one."""
    class Stub:
        @staticmethod
        def init(key):
            return {"w_gate": jnp.zeros((2, 4, 8), jnp.bfloat16),
                    "proj": {"w": jnp.zeros((4, 8), jnp.bfloat16)}}

    with pytest.raises(ValueError, match="no initializer"):
        serving.init_weights(Stub, SEED)
    p = serving.init_weights(
        Stub, SEED, {"w_gate": lambda k, shape, dtype: jnp.full(shape, 3.0)})
    assert p["w_gate"].dtype == jnp.bfloat16
    assert (np.asarray(p["w_gate"], np.float32) == 3.0).all()
    assert np.asarray(p["proj"]["w"], np.float32).std() > 0
