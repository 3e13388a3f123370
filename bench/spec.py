"""What ``BENCHMARK.json`` names, found by name under this directory.

A cell (``workloads`` entry) names a configuration and a traffic mix. A
configuration is ``configs/<name>.json`` (the file its entry names), a
traffic mix is ``traffic/<name>.json``, and a per-layer metric is read by
``layers/<name>.py``. A configuration's ``"deployment"`` key names the
module ``deployments/<name>.py`` that knows its store. Adding a cell, a
mix, a metric or a deployment adds files and entries; no code here names
one.

A deployment module provides, for a configuration's dict ``config``:

* ``schedule(config, traffic, seed)``: the host op schedule, drawn from
  ``seed`` (any whole number) and the traffic file's dict;
* ``store(config, schedule)``: ``(lattice, topology, StoreSpec)`` built
  through ``repro``'s public API, the per-object byte weights in the
  ``StoreSpec``;
* ``reference(config, schedule, rounds, control=None)``: the plain
  reference's outputs, a dict of host arrays: ``final_x`` (a pytree of
  [B, N, ...] leaves, as the store's final states), ``acked`` (the join
  of every applied update, leaves [B, ...]), and [B, T] ``tx``, ``mem``,
  ``cpu``, ``max_mem_node``, ``uniform`` and ``tx_bytes``; with
  ``control`` one of ``CONTROLS``, that control's outputs;
* ``CONTROLS``: the names of its controls (``control.py``);
* ``leq(a, b)``: the elementwise order of two states' leaves (pytrees of
  equal shapes), a bool array of one leaf's shape;
* ``round_bytes(config)``: the bytes one sync round must read and write,
  whatever implements it.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
DEPLOYMENTS = HERE / "deployments"


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


def _load(path: Path, prefix: str):
    """The module at ``path``, registered in ``sys.modules`` (as a
    dataclass defined in it needs) under ``prefix`` + its stem."""
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str):
    """The ``read(ctx)`` function of ``layers/<metric>.py``."""
    return _load(HERE / "layers" / f"{metric}.py", "bench_layer_").read


def deployment(cell: Cell):
    """The module ``deployments/<name>.py`` that the cell's configuration
    names under ``"deployment"``."""
    return _load(DEPLOYMENTS / f"{cell.config['deployment']}.py",
                 "bench_deployment_")
