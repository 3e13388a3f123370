"""What ``BENCHMARK.json`` names, found by name under this directory.

A cell (``workloads`` entry) names a configuration and a traffic mix. A
configuration is ``configs/<name>.json`` (the file its entry names), a
traffic mix is ``traffic/<name>.json``, and a per-layer metric is read by
``layers/<name>.py``. Adding a cell, a mix or a metric adds files and
entries; no code here names one.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple          # metric entries this cell reports
    per_layer: tuple

    @property
    def rounds(self) -> int:
        return int(self.config["rounds"])

    @property
    def active_rounds(self) -> int:
        return int(self.traffic["active_rounds"])


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``name`` of ``bench_file`` with its configuration and
    traffic loaded."""
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file.name}; one of "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    if not 1 <= int(traffic["active_rounds"]) <= int(config["rounds"]):
        raise ValueError(f"{name}: traffic {w['traffic']!r} wants "
                         f"{traffic['active_rounds']} active rounds of the "
                         f"configuration's {config['rounds']}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)))


def layer_reader(metric: str):
    """The ``read(ctx)`` function of ``layers/<metric>.py``."""
    path = HERE / "layers" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
