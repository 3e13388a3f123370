#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

A control is the plain reference put in the program's place with one of
the configuration's guarantees broken; the cell's deployment module names
its controls (``CONTROLS``; the Retwis store's ``lossy`` loses the
messages of each node's first neighbour slot, ``unsent`` keeps node 0's
updates from leaving it). For each seed and control it runs the reference
and the control at the cell's own size on the chip, compares the
control's outputs with the same code that judges the program
(``check.compare``), and prints each number beside its limit. Every seed
has to come out not correct. The benchmark's own runs never run this.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check, spec  # noqa: E402


def readings(cell: spec.Cell, seed: int) -> dict:
    """``{control: numbers}`` for one seed."""
    dep = spec.deployment(cell)
    schedule = dep.schedule(cell.config, cell.traffic, seed)
    ref = dep.reference(cell.config, schedule, cell.rounds)
    return {name: check.compare(
                [dep.reference(cell.config, schedule, cell.rounds, name)],
                ref, dep.leq)[0]
            for name in dep.CONTROLS}


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    import jax

    dev = jax.devices()[0]
    for seed in args.seeds:
        t0 = time.perf_counter()
        for name, numbers in readings(cell, seed).items():
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "control": name, "device": dev.device_kind,
                              "correct": check.within(numbers),
                              "seconds": time.perf_counter() - t0,
                              "checks": {k: {"value": v,
                                             "limit": check.LIMITS[k]}
                                         for k, v in numbers.items()}}),
                  flush=True)


if __name__ == "__main__":
    main()
