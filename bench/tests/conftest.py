"""CPU tests of the benchmark harness: ``python -m pytest bench/tests`` from
the root of a checkout (``JAX_PLATFORMS=cpu``; kernels run interpreted)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def small_cell():
    """A cell of the real shapes' kind at a size the CPU runs in seconds:
    the retwis-bprr configuration and paper mix, cut to 24 objects on 8
    nodes and 6 rounds (3 active)."""
    from bench import spec

    def make(algorithm="bprr", objects=24, nodes=8, rounds=6, active=3):
        full = spec.cell("retwis-bprr.paper")
        config = dict(full.config, algorithm=algorithm, objects=objects,
                      nodes=nodes, rounds=rounds, chunk_rounds=3)
        traffic = dict(full.traffic, active_rounds=active)
        return spec.Cell(name="small", chips=1, config=config,
                         traffic=traffic, end_to_end=full.end_to_end,
                         per_layer=full.per_layer)

    return make
