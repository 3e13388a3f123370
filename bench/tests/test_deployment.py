"""A deployment is the module its configuration names: the harness reaches
the store, the reference, the order and the byte count only through it.
A second deployment, a toy store of LWW maps (``data/toy_lww.py``: states
of two lex-pair leaves, its own schedule and numpy reference), runs
through the unchanged harness and is judged correct, and faults planted
under it are caught. The Retwis module gives what the calls it replaced
gave."""

import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import check, control, generator, reference, roofline, run, spec

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture
def toy_cell(monkeypatch):
    """A cell of the toy deployment: 12 objects of 8 keys at 6 nodes of a
    degree-4 mesh, 7 rounds of which 4 active."""
    monkeypatch.setattr(spec, "DEPLOYMENTS", DATA)
    full = spec.cell("retwis-bprr.paper")

    def make(algorithm="bprr"):
        config = {"name": "toy", "deployment": "toy_lww", "objects": 12,
                  "nodes": 6, "degree": 4, "keys": 8, "weight_bytes": 16,
                  "algorithm": algorithm, "engine": "mega", "layout": "rows",
                  "chunk_rounds": 3, "rounds": 7}
        traffic = {"active_rounds": 4, "write_prob": 0.4}
        return spec.Cell(name="toy", chips=1, config=config, traffic=traffic,
                         end_to_end=full.end_to_end, per_layer=full.per_layer)

    return make


def execute(cell, seed=2**31 + 5):
    res, checks = run.execute(cell, seed, 0.1, 0, jax.devices(),
                              time.perf_counter())
    return res, {k: v["value"] for k, v in checks.items()}


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
def test_second_deployment_runs_correct(toy_cell, algorithm):
    cell = toy_cell(algorithm)
    res, numbers = execute(cell)
    assert res["correct"], numbers
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert all(v == 0 for v in numbers.values())
    # the work is real: writes spread and every replica's state is a pair
    dep = spec.deployment(cell)
    sched = dep.schedule(cell.config, cell.traffic, 2**31 + 5)
    ref = dep.reference(cell.config, sched, cell.rounds)
    assert ref["tx"].sum() > 0 and len(ref["final_x"]) == 2


def _planted(alter):
    def plant(monkeypatch):
        import repro.sync.store as store

        inner = store.collect_result

        def collect(*a, **kw):
            sim = inner(*a, **kw)
            return sim._replace(final_x=alter(
                tuple(np.array(leaf) for leaf in sim.final_x)))

        monkeypatch.setattr(store, "collect_result", collect)

    return plant


def _value_altered(x):
    """One replica's value at its newest key raised: the timestamps
    stand."""
    ts, val = x
    i = np.unravel_index(np.argmax(ts), ts.shape)
    val[i] += 1
    return ts, val


def _writes_lost(x):
    """Every replica of object 0 back to bottom."""
    ts, val = x
    ts[0], val[0] = 0, 0
    return ts, val


@pytest.mark.parametrize("fault, number", [
    (_value_altered, "objects_state_mismatch"),
    (_writes_lost, "acked_updates_missing")])
def test_second_deployment_fault_is_caught(toy_cell, monkeypatch, fault,
                                           number):
    _planted(fault)(monkeypatch)
    res, numbers = execute(toy_cell())
    assert not res["correct"], numbers
    assert numbers[number] > 0


def test_second_deployment_control_is_not_correct(toy_cell):
    got = control.readings(toy_cell(), 7)
    assert set(got) == {"unsent"}
    assert got["unsent"]["objects_unconverged"] > 0
    assert got["unsent"]["acked_updates_missing"] > 0


def test_lex_order_is_judged_by_pairs():
    """``acked_updates_missing`` reads the deployment's order over both
    leaves: a newer timestamp holds an older one whatever the values, an
    equal one needs the value."""
    fx = (np.array([[[3, 2]]]), np.array([[[1, 4]]]))       # [B, N, keys]
    out = {m: np.zeros((1, 1)) for m in check.METRICS}
    out["final_x"] = fx
    ref = dict(out, acked=(np.array([[2, 2]]), np.array([[5, 5]])))
    toy = spec._load(DATA / "toy_lww.py", "bench_test_")
    numbers = check.compare_one(out, ref, toy.leq)
    assert numbers["acked_updates_missing"] == 1      # (2, 4) < (2, 5)
    assert numbers["objects_state_mismatch"] == 0


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_retwis_module_gives_what_the_old_calls_gave(small_cell, algorithm,
                                                     seed):
    cell = small_cell(algorithm, objects=42, nodes=8, rounds=9, active=5)
    c = cell.config
    dep = spec.deployment(cell)
    counts = dep.schedule(c, cell.traffic, seed)
    np.testing.assert_array_equal(counts, generator.update_counts(
        cell.traffic, c["objects"], c["nodes"], seed % (1 << 64)))
    w = np.asarray(c["weights_bytes"], np.float64)[np.arange(42) % 3]
    for ctl in (None,) + dep.CONTROLS:
        got = dep.reference(c, counts, cell.rounds, ctl)
        old = reference.simulate(counts, nodes=8, degree=4, slots=64,
                                 algorithm=algorithm, rounds=9, control=ctl)
        assert set(got) == set(old) | {"tx_bytes"}
        for k, v in old.items():
            np.testing.assert_array_equal(got[k], v)
        np.testing.assert_array_equal(got["tx_bytes"],
                                      old["tx"] * w[:, None])
    assert dep.round_bytes(c) == roofline.round_bytes(algorithm, 42, 8, 4,
                                                      64)


def test_retwis_module_builds_the_old_store(small_cell):
    from repro.sync import workloads

    cell = small_cell()
    c = cell.config
    dep = spec.deployment(cell)
    counts = dep.schedule(c, cell.traffic, 5)
    lattice, topo, sspec = dep.store(c, counts)
    old = workloads.versioned_slot_op(counts, 64)
    assert sspec.op_fn.apply == old.apply
    np.testing.assert_array_equal(sspec.op_fn.operands[0], old.operands[0])
    np.testing.assert_array_equal(sspec.weights,
                                  np.asarray([20, 301, 39] * 8, np.float64))
    assert sspec.objects == 24 and lattice.kernel_kind == "max"
    assert topo.num_nodes == 8


@pytest.mark.parametrize("name, want", [
    ("retwis-bprr.paper", 4_992_000_000), ("retwis-classic.paper",
                                            1_920_000_000)])
def test_round_bytes_of_the_cells(name, want):
    cell = spec.cell(name)
    assert spec.deployment(cell).round_bytes(cell.config) == want
