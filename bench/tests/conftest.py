"""Puts ``bench/`` and the program's ``src/`` on the import path, and keeps
the CPU tests small: ``tiny_cell`` builds a cell of the benchmark's own kind
at widths a test run can hold."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"hidden_size": 128, "num_hidden_layers": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "intermediate_size": 256, "vocab_size": 512}
TINY_SERVING = {"slots": 4, "max_len": 64}
TINY_TRAFFIC = {"prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
                "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 12},
                "block": 8, "trace_seconds": 1}
# Set from CPU readings at this size, seeds 2**40+11, 5, 99: sound runs read
# a widest gap of at most 0.0049 (float) and 0.0145 (int8) and a mean of at
# most 0.0001 and 0.0004; the controls read at least 0.42 and 0.85 widest,
# 0.014 and 0.23 mean.
TINY_LIMITS = {"max_gap": 0.1, "mean_gap": 0.005}


def tiny_cell(config: str, traffic: str, rate: float = 40.0):
    """The cell of ``config`` under ``traffic``, cut to test size, with the
    metrics ``BENCHMARK.json`` gives a cell of that name and the limits set
    for this size."""
    from harness.spec import Cell, reports
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    cfg = copy.deepcopy(cfg)
    cfg["model"].update(TINY_MODEL)
    cfg["model"].pop("head_dim", None)
    cfg["serving"].update(TINY_SERVING)
    cfg["limits"] = dict(TINY_LIMITS)
    mix.update(copy.deepcopy(TINY_TRAFFIC))
    if mix["arrival"]["kind"] == "poisson":
        mix["arrival"]["rate_per_s"] = rate
    return Cell(name=name, chips=1, config=cfg, traffic=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if reports(m, name)])


@pytest.fixture
def cell_factory():
    return tiny_cell
