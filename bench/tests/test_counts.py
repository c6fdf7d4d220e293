"""Work counted from shapes, checked against the program's own parameter
tree and against hand counts."""
from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from conftest import BENCH
from harness import spec

LLAMA = spec.family("llama")
ModelShapes = LLAMA.ModelShapes


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,params", [
    ("minicpm-2b.float", 2_724_880_896),
    ("deepseek-coder-33b-l8.float", 4_705_082_368),
    ("deepseek-coder-33b-l31.tp4.float", 16_902_710_272)])
def test_param_count(name, params):
    cfg = config(name)
    shapes = ModelShapes.from_model(cfg["model"])
    assert shapes.param_count() == params
    from repro.models.model import build_model
    tree = jax.eval_shape(build_model(LLAMA.model_config(cfg)).init,
                          jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree)) == params


def test_minicpm_bytes():
    s = ModelShapes.from_model(config("minicpm-2b.float")["model"])
    assert s.kv_bytes_per_token == 368_640
    assert s.weight_bytes("float") == 2 * (s.proj_params + 2304 * 122753)
    assert s.weight_bytes("int8") < s.weight_bytes("float")


def test_flops_do_not_depend_on_the_tier():
    s = ModelShapes.from_model(config("minicpm-2b.float")["model"])
    n = 100
    attn = 40 * 4 * 36 * 64 * (n * (n + 1) // 2)
    assert s.prefill_flops(n) == 2 * n * s.proj_params + attn \
        + 2 * 2304 * 122753
    assert s.decode_flops(n) == 2 * s.proj_params + 40 * 4 * 36 * 64 * n \
        + 2 * 2304 * 122753
