#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]

A control is the plain reference put in the program's place with one of
the configuration's guarantees broken (``reference.CONTROLS``: ``lossy``
loses the messages of each node's first neighbour slot, ``unsent`` keeps
node 0's updates from leaving it). For each seed and control it runs the
reference and the control at the cell's own size on the chip, compares
the control's outputs with the same code that judges the program
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

from bench import check, generator, reference, spec  # noqa: E402


def outputs(cell: spec.Cell, counts, control=None) -> dict:
    """The reference's (with ``control``: that control's) outputs in the
    form ``check.compare`` reads for a program call."""
    import numpy as np

    c = cell.config
    out = reference.simulate(counts, nodes=c["nodes"], degree=c["degree"],
                             slots=c["slots"], algorithm=c["algorithm"],
                             rounds=cell.rounds, control=control)
    w = np.asarray(c["weights_bytes"], np.float64)
    out["weights"] = w[np.arange(c["objects"]) % len(w)]
    out["tx_bytes"] = out["tx"].astype(np.float64) * out["weights"][:, None]
    return out


def readings(cell: spec.Cell, seed: int) -> dict:
    """``{control: numbers}`` for one seed."""
    c = cell.config
    counts = generator.update_counts(cell.traffic, c["objects"], c["nodes"],
                                     seed % (1 << 64))
    ref = outputs(cell, counts)
    return {name: check.compare([outputs(cell, counts, name)], ref)[0]
            for name in reference.CONTROLS}


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
