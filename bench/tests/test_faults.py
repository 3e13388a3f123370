"""Drive whole runs on the CPU (the chip check skipped) with the timed path
sound and with faults planted underneath it: ``correct`` holds for the
sound run and fails for each fault. The control (``control.py``) fails
the same comparison. One chip holds the whole store, so there is no
exchange between chips to leave out."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, control, run, spec


def execute(cell):
    res, checks = run.execute(cell, 2**31 + 3, 0.1, 0, jax.devices(),
                              time.perf_counter())
    return res, {k: v["value"] for k, v in checks.items()}


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
def test_sound_run_is_correct(small_cell, algorithm):
    res, numbers = execute(small_cell(algorithm))
    assert res["correct"], numbers
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"object_rounds_per_s", "setup_s"}
    assert all(v == 0 for v in numbers.values())


def _unchanged_step(self, carry, op_delta, faults=None, **_):
    """A round step that returns its state unchanged."""
    from repro.sync.algorithms import RoundMetrics, metric_dtype

    z = jnp.zeros(carry.buf_elems.shape[:1], metric_dtype())
    return carry, RoundMetrics(tx=z, mem=z, cpu=z, max_mem_node=z)


def _half_store(monkeypatch):
    """Half of the objects left out: their results are copied from the
    half that ran."""
    import repro.sync as sync

    inner = sync.simulate_store

    def half(*a, **kw):
        res = inner(*a, **kw)
        sim = res.sim
        h = sim.tx.shape[0] // 2

        def fill(v):
            v = np.array(v)
            v[h:2 * h] = v[:h]
            return v

        return res._replace(sim=sim._replace(
            tx=fill(sim.tx), mem=fill(sim.mem), cpu=fill(sim.cpu),
            max_mem_node=fill(sim.max_mem_node),
            uniform=fill(sim.uniform), final_x=fill(sim.final_x)))

    monkeypatch.setattr(sync, "simulate_store", half)


def _altered(field):
    """One answer altered where it is produced (one slot of one replica,
    or one object's count in one round)."""

    def plant(monkeypatch):
        import repro.sync.store as store

        inner = store.collect_result

        def collect(*a, **kw):
            sim = inner(*a, **kw)
            v = np.array(getattr(sim, field))
            v[(0,) * v.ndim] += 1
            return sim._replace(**{field: v})

        monkeypatch.setattr(store, "collect_result", collect)

    return plant


FAULTS = {
    "state_unchanged": lambda mp: mp.setattr(
        "repro.sync.algorithms.SyncAlgorithm.round_step", _unchanged_step),
    "half_the_objects": _half_store,
    "state_altered": _altered("final_x"),
    "count_altered": _altered("tx"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_caught(small_cell, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    res, numbers = execute(small_cell())
    assert not res["correct"], numbers
    assert res["failed"] >= 1


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_controls_are_not_correct(small_cell, algorithm, seed):
    cell = small_cell(algorithm, objects=60, nodes=10, rounds=8, active=4)
    got = control.readings(cell, seed)
    assert set(got) == {"lossy", "unsent"}
    for numbers in got.values():
        assert not check.within(numbers), numbers
        assert numbers["objects_metric_mismatch"] > 0
    # node 0's updates never leave it: replicas disagree and miss them
    assert got["unsent"]["objects_unconverged"] > 0
    assert got["unsent"]["acked_updates_missing"] > 0


def test_reference_agrees_with_program_engine(small_cell):
    """The reference and the program's plain jnp engine agree exactly at a
    small size, for both algorithms (the comparison's lower reading)."""
    from repro.core import value_lattices as vl
    from repro.core.lattice import MapLattice
    from repro.sync import StoreSpec, simulate_store, topology, workloads

    for algorithm in ("bprr", "classic"):
        cell = small_cell(algorithm, objects=42, nodes=8, rounds=9, active=5)
        dep = spec.deployment(cell)
        counts = dep.schedule(cell.config, cell.traffic, 11)
        ref = dep.reference(cell.config, counts, cell.rounds)
        res = simulate_store(
            algorithm, MapLattice(64, vl.max_int()).build(),
            topology.partial_mesh(8, 4),
            StoreSpec(objects=42, op_fn=workloads.versioned_slot_op(
                counts, 64), weights=dep.weights(cell.config)),
            5, 4, engine="reference", track_convergence=True)
        out = check.call_outputs(res)
        numbers, bad = check.compare([out], ref, dep.leq)
        assert bad == 0 and check.within(numbers), (algorithm, numbers)
