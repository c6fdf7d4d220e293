"""Find a cell's configuration, traffic mix, family and metrics by the names
that ``BENCHMARK.json`` and the configuration file give them."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
FAMILIES = "bench/harness/families"     # under a checkout's root


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    end_to_end: list        # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    root: pathlib.Path = ROOT   # the checkout the family module is found in

    @property
    def tier(self) -> str:
        return self.config["serving"]["tier"]

    @property
    def family(self):
        """The module of the configuration's ``family``."""
        return family(self.config["family"], self.root)


def reports(metric: dict, cell: str) -> bool:
    """Whether a cell of that name reports the metric."""
    return "workloads" not in metric or cell in metric["workloads"]


def family(name: str, root: pathlib.Path = ROOT):
    """``bench/harness/families/<name>.py`` under ``root``, loaded by path
    once per process, so that a family is added with a file and no edit."""
    path = pathlib.Path(root).resolve() / FAMILIES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no module for model family {name!r}: looked for "
                         f"{path}")
    return _load(path)


@functools.lru_cache(maxsize=None)
def _load(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


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
                per_layer=[m for m in bench["per_layer"] if reports(m, name)],
                root=pathlib.Path(root))
