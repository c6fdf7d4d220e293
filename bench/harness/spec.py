"""Find a cell's configuration, traffic mix and metrics by the names that
``BENCHMARK.json`` gives them."""
from __future__ import annotations

import dataclasses
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # metric entries of BENCHMARK.json this cell reports
    per_layer: list

    @property
    def tier(self) -> str:
        return self.config["serving"]["tier"]


def reports(metric: dict, cell: str) -> bool:
    """Whether a cell of that name reports the metric."""
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if reports(m, name)])


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: every size
    from the file, nothing from the program's own registry."""
    from repro.configs.base import ModelConfig
    m = config["model"]
    return ModelConfig(
        name=config["name"], family="dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        d_ff=m["intermediate_size"], vocab=m["vocab_size"],
        head_dim=m.get("head_dim", 0), rope_theta=float(m["rope_theta"]),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]), act=m["hidden_act"],
        param_dtype=m["torch_dtype"])
