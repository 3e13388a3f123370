"""Store-engine invariant (DESIGN.md §15): every cell of a
``simulate_store`` run is bit-identical — final states AND all metrics —
to a standalone per-object ``simulate()``, for every algorithm, on both
engines, with and without a store-shared fault schedule.

Plus: weighted element accounting (per-object byte weights as engine
metrics, ``Lattice.wsize``), the fused kernels' ``rows`` vs ``grid``
batch layouts, object-axis sharding, StoreSpec validation, and
property-based tests for ``sync/workloads.py`` (probabilities normalize,
streams are seed-deterministic, op-mix marginals match the spec,
vectorized update counts match the reference loop).
"""

import gc
import subprocess
import sys
import weakref
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from conftest import subprocess_env
from test_sweep import (
    SEEDS,
    assert_cell_identical,
    bitgset_sweep_ops,
    gset_cell_op,
    gset_sweep_op,
)

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import BatchWeights, BitGSet, GCounter, GSet, product
from repro.core.lattice import Lattice, MapLattice, align_weights
from repro.core import value_lattices as vl
from repro.sync import (
    ALGORITHMS,
    FaultSchedule,
    StoreSpec,
    resume_store,
    simulate,
    simulate_store,
    topology,
)
from repro.sync import TelemetrySpec
from repro.sync import workloads as W
from repro.obs.trace import TraceLog
from repro.sync.store import (
    _ProgramCache,
    clear_program_cache,
    program_cache_info,
)

N, T, Q, B = 7, 5, 8, 3


def store_schedule(topo):
    """One composite store-wide schedule: loss ∘ partition ∘ churn, with a
    fault-free drain tail so convergence can be asserted."""
    n = topo.num_nodes
    return FaultSchedule.bernoulli(topo, T, 0.2, seed=2).compose(
        FaultSchedule.partition(
            topo, T, start=1, stop=T - 1,
            groups=(np.arange(n) >= n // 2).astype(np.int32))).compose(
        FaultSchedule.churn(topo, T, [(n // 2, 1, T - 1)]))


# -- the bit-identity invariant ----------------------------------------------

@pytest.mark.parametrize("engine", ["reference", "fused", "mega"])
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_store_cells_bit_identical_fault_free(algo, engine):
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS))
    res = simulate_store(algo, lat, topo, spec, active_rounds=T,
                         quiet_rounds=Q, engine=engine)
    assert res.objects == B
    for b, seed in enumerate(SEEDS):
        single = simulate(algo, lat, topo, gset_cell_op(seed),
                          active_rounds=T, quiet_rounds=Q, engine=engine)
        assert_cell_identical(res.object_result(b), single,
                              f"store/{algo}/{engine}/obj{b}")


@pytest.mark.parametrize("engine", ["reference", "fused", "mega"])
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_store_cells_bit_identical_shared_faults(algo, engine):
    """Unlike a sweep, ONE schedule hits every object — per-object runs
    with that same schedule must match each store cell bit-for-bit, and
    the drain tail must converge every object."""
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    sched = store_schedule(topo)
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS), faults=sched)
    res = simulate_store(algo, lat, topo, spec, active_rounds=T,
                         quiet_rounds=Q, engine=engine)
    convs = res.convergence_round()
    assert convs.shape == (B,)
    for b, seed in enumerate(SEEDS):
        single = simulate(algo, lat, topo, gset_cell_op(seed),
                          active_rounds=T, quiet_rounds=Q, engine=engine,
                          faults=sched, track_convergence=True)
        assert_cell_identical(res.object_result(b), single,
                              f"store/{algo}/{engine}/faulted/obj{b}")
        assert int(convs[b]) == single.convergence_round()
        assert int(convs[b]) >= 0


@pytest.mark.parametrize("engine", ["fused", "mega"])
@pytest.mark.parametrize("layout", ["rows", "grid"])
def test_store_layouts_bit_identical_bitor(layout, engine):
    """The packed bitor kernel kind through both object-axis layouts."""
    lat, cell_op, sweep_op = bitgset_sweep_ops()
    topo = topology.tree(N)
    res = simulate_store("bprr", lat, topo,
                         StoreSpec(objects=2, op_fn=sweep_op),
                         active_rounds=T, quiet_rounds=Q, engine=engine,
                         layout=layout)
    single = simulate("bprr", lat, topo, cell_op, active_rounds=T,
                      quiet_rounds=Q, engine=engine)
    for b in range(2):
        assert_cell_identical(res.object_result(b), single,
                              f"bitgset/{layout}/{engine}/{b}")


def test_store_digest_rows_layout():
    """digest_driven through the fused rows layout: the digest + extract
    kernels fold the object axis into tile rows (aux carries the object
    axis)."""
    topo = topology.ring(N)
    lat = GSet(universe=N * T).lattice
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS))
    rows = simulate_store("digest_driven", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q, engine="fused", layout="rows")
    grid = simulate_store("digest_driven", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q, engine="fused", layout="grid")
    ref = simulate_store("digest_driven", lat, topo, spec, active_rounds=T,
                         quiet_rounds=Q, engine="reference")
    for b in range(B):
        assert_cell_identical(rows.object_result(b), grid.object_result(b),
                              f"digest-rows-vs-grid/{b}")
        assert_cell_identical(rows.object_result(b), ref.object_result(b),
                              f"digest-rows-vs-ref/{b}")


# -- weighted element accounting ---------------------------------------------

def test_weighted_accounting_matches_manual():
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    w = np.asarray([20.0, 301.0, 39.0])
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS), weights=w)
    res = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                         quiet_rounds=Q)
    tx = np.asarray(res.tx, np.float64)
    np.testing.assert_array_equal(res.tx_bytes, tx * w[:, None])
    np.testing.assert_array_equal(res.store_tx_bytes,
                                  (tx * w[:, None]).sum(axis=0))
    assert res.total_tx_bytes == float((tx * w[:, None]).sum())
    # weighted final-state footprint: every object converged to the full
    # N*T universe, so bytes/node = universe × weight
    np.testing.assert_array_equal(
        res.final_state_bytes,
        np.broadcast_to(w[:, None] * (N * T), (B, N)))


def test_wsize_reduces_to_size():
    """wsize(x, 1) == size(x) across lattice constructions."""
    for lat, x in [
        (GSet(universe=12).lattice,
         jnp.arange(24).reshape(2, 12) % 3 == 0),
        (GCounter(6).lattice, jnp.arange(12).reshape(2, 6)),
        (BitGSet(universe=40).lattice,
         jnp.arange(4, dtype=jnp.uint32).reshape(2, 2)),
    ]:
        np.testing.assert_array_equal(np.asarray(lat.wsize(x, 1)),
                                      np.asarray(lat.size(x)))


def test_wsize_per_slot_weights():
    lat = GSet(universe=4).lattice
    x = jnp.asarray([[True, False, True, True]])
    w = jnp.asarray([1.0, 10.0, 100.0, 1000.0])
    np.testing.assert_array_equal(np.asarray(lat.wsize(x, w)), [1101.0])


# -- spec validation ----------------------------------------------------------

def test_store_spec_validation():
    topo = topology.partial_mesh(N, 4)
    other = topology.tree(N)
    lat = GSet(universe=N * T).lattice
    with pytest.raises(ValueError):
        StoreSpec(objects=0, op_fn=lambda x, t: x)
    with pytest.raises(ValueError):
        StoreSpec(objects=3, op_fn=lambda x, t: x, weights=np.ones(2))
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS),
                     faults=FaultSchedule.none(other, T))
    with pytest.raises(ValueError):        # schedule bound to another topo
        simulate_store("bprr", lat, topo, spec, active_rounds=T)
    with pytest.raises(ValueError):        # unknown layout
        simulate_store("bprr", lat, topo,
                       StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS)),
                       active_rounds=T, layout="diagonal")


# -- sharding -----------------------------------------------------------------

def test_store_shard_single_device_noop():
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS))
    a = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                       quiet_rounds=Q, shard=False)
    b = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                       quiet_rounds=Q, shard=True)
    for f in ("tx", "mem", "cpu", "max_mem_node"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    np.testing.assert_array_equal(np.asarray(a.final_x),
                                  np.asarray(b.final_x))


SHARD_SCRIPT = r"""
import tempfile
import jax, jax.numpy as jnp, numpy as np
assert len(jax.devices()) == 4, jax.devices()
from repro.core import GSet
from repro.launch import mesh as launch_mesh
from repro.sync import (FaultSchedule, StoreSpec, resume_store,
                        simulate_store, topology)

# 2-D ("object", "config") store mesh geometry (DESIGN.md SS16)
assert dict(launch_mesh.store_mesh().shape) == {"object": 4, "config": 1}
assert dict(launch_mesh.store_mesh(config_devices=2).shape) == \
    {"object": 2, "config": 2}

N, T, Q, B = 7, 5, 8, 7        # B=7: auto-pads to 8 across 4 devices
topo = topology.partial_mesh(N, 4)
lat = GSet(universe=N * T).lattice

def op_b(x, t):
    # shard-agnostic: the object extent comes from x, never a closure
    b = x.shape[0]
    ids = jnp.arange(N) * T + jnp.minimum(t, T - 1)
    d = jnp.zeros((b, N, N * T), jnp.bool_)
    return d.at[:, jnp.arange(N), ids].set(True)

sched = FaultSchedule.bernoulli(topo, T, 0.3, seed=5)
spec = StoreSpec(objects=B, op_fn=op_b, faults=sched,
                 weights=np.arange(1.0, B + 1))
a = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                   quiet_rounds=Q, shard=False)
b = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                   quiet_rounds=Q, shard=True)
for f in ("tx", "mem", "cpu", "max_mem_node", "uniform"):
    np.testing.assert_array_equal(getattr(a, f), getattr(b, f), err_msg=f)
np.testing.assert_array_equal(np.asarray(a.final_x), np.asarray(b.final_x))
np.testing.assert_array_equal(a.final_state_bytes, b.final_state_bytes)

# chunked + in-scan reduced metrics + checkpoint/resume, all sharded
with tempfile.TemporaryDirectory() as d:
    c = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                       quiet_rounds=Q, shard=True, chunk_rounds=4,
                       object_metrics=False, checkpoint=d)
    assert c.sim.tx.shape[0] == 4, c.sim.tx.shape   # per-shard partials
    np.testing.assert_array_equal(a.store_tx, c.store_tx)
    np.testing.assert_array_equal(a.store_mem, c.store_mem)
    np.testing.assert_array_equal(a.store_cpu, c.store_cpu)
    np.testing.assert_array_equal(a.store_max_mem_node, c.store_max_mem_node)
    assert a.store_convergence_round() == c.store_convergence_round()
    r = resume_store("bprr", lat, topo, spec, active_rounds=T,
                     quiet_rounds=Q, shard=True, object_metrics=False,
                     checkpoint=d, step=4)
    np.testing.assert_array_equal(c.sim.tx, r.sim.tx)
    np.testing.assert_array_equal(c.sim.uniform, r.sim.uniform)
    np.testing.assert_array_equal(np.asarray(c.final_x),
                                  np.asarray(r.final_x))
print("STORE_SHARD_OK")
"""


def test_store_shard_map_multi_device_subprocess():
    """Object-axis shard_map equivalence on 4 forced host devices: the
    store's fault masks replicate (shared network) while carries shard.
    Subprocess because XLA device count is locked at jax import."""
    proc = subprocess.run(
        [sys.executable, "-c", SHARD_SCRIPT],
        env=subprocess_env(4), capture_output=True, text=True, timeout=420,
        cwd=str(Path(__file__).resolve().parents[1]))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "STORE_SHARD_OK" in proc.stdout


# -- memory-bounded scale-out (DESIGN.md §16) ---------------------------------

def _scale_fixture():
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS),
                     weights=np.arange(1.0, B + 1),
                     faults=store_schedule(topo))
    return topo, lat, spec


def _assert_store_identical(a, b):
    for f in ("tx", "mem", "cpu", "max_mem_node", "uniform"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                      err_msg=f)
        assert getattr(a, f).dtype == getattr(b, f).dtype, f
    np.testing.assert_array_equal(np.asarray(a.final_x),
                                  np.asarray(b.final_x))
    np.testing.assert_array_equal(a.final_state_bytes, b.final_state_bytes)


def test_store_chunked_bit_identical():
    """Chunked scan (donated carry, host-offloaded metrics) ==
    monolithic scan, bit for bit, including an uneven tail chunk."""
    topo, lat, spec = _scale_fixture()
    mono = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q)
    for chunk in (1, 4, 5, T + Q, T + Q + 9):
        chunked = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                                 quiet_rounds=Q, chunk_rounds=chunk)
        _assert_store_identical(mono, chunked)


class _KilledAfterSaves(Checkpointer):
    """Checkpointer that dies right after its Nth successful save —
    simulates a job killed at a chunk boundary."""

    def __init__(self, directory, die_after: int):
        super().__init__(directory)
        self.die_after = die_after

    def save(self, step, state, extra=None):
        out = super().save(step, state, extra)
        self.die_after -= 1
        if self.die_after <= 0:
            raise KeyboardInterrupt("killed after checkpoint save")
        return out


def test_store_resume_after_kill_bit_identical(tmp_path):
    """Kill the run right after chunk 1's checkpoint lands, resume from
    the bundle, and get the uninterrupted run's exact result."""
    topo, lat, spec = _scale_fixture()
    chunk = 4
    full = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q, chunk_rounds=chunk)
    with pytest.raises(KeyboardInterrupt):
        simulate_store("bprr", lat, topo, spec, active_rounds=T,
                       quiet_rounds=Q, chunk_rounds=chunk,
                       checkpoint=_KilledAfterSaves(tmp_path, die_after=1))
    ck = Checkpointer(tmp_path)
    assert ck.available_steps() == [chunk]       # only chunk 1 survived
    res = resume_store("bprr", lat, topo, spec, active_rounds=T,
                       quiet_rounds=Q, checkpoint=ck)
    _assert_store_identical(full, res)
    # ...and the resumed run kept checkpointing from where it restarted
    assert ck.available_steps()[-1] == T + Q


def test_store_resume_every_boundary_bit_identical(tmp_path):
    topo, lat, spec = _scale_fixture()
    chunk = 4
    full = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q, chunk_rounds=chunk,
                          checkpoint=tmp_path)
    ck = Checkpointer(tmp_path)
    assert ck.available_steps() == [4, 8, 12, T + Q]
    for step in ck.available_steps():
        res = resume_store("bprr", lat, topo, spec, active_rounds=T,
                           quiet_rounds=Q, checkpoint=tmp_path, step=step)
        _assert_store_identical(full, res)


def test_store_resume_rejects_mismatched_run(tmp_path):
    topo, lat, spec = _scale_fixture()
    simulate_store("bprr", lat, topo, spec, active_rounds=T,
                   quiet_rounds=Q, chunk_rounds=4, checkpoint=tmp_path)
    with pytest.raises(ValueError, match="different store run"):
        resume_store("state", lat, topo, spec, active_rounds=T,
                     quiet_rounds=Q, checkpoint=tmp_path)
    with pytest.raises(ValueError, match="different store run"):
        resume_store("bprr", lat, topo, spec, active_rounds=T + 1,
                     quiet_rounds=Q, checkpoint=tmp_path)
    with pytest.raises(ValueError, match="no checkpoint for round"):
        resume_store("bprr", lat, topo, spec, active_rounds=T,
                     quiet_rounds=Q, checkpoint=tmp_path, step=3)


def test_store_checkpoint_requires_chunking(tmp_path):
    topo, lat, spec = _scale_fixture()
    with pytest.raises(ValueError, match="chunk_rounds"):
        simulate_store("bprr", lat, topo, spec, active_rounds=T,
                       checkpoint=tmp_path)


def test_store_reduced_metrics_exact_aggregates():
    """object_metrics=False reduces inside the scan; the store-level
    sums/maxes are bit-identical (integer partials) and per-object
    views raise with a pointer at the knob."""
    topo, lat, spec = _scale_fixture()
    full = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q)
    red = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                         quiet_rounds=Q, object_metrics=False,
                         chunk_rounds=4)
    assert red.objects == B
    np.testing.assert_array_equal(full.store_tx, red.store_tx)
    np.testing.assert_array_equal(full.store_mem, red.store_mem)
    np.testing.assert_array_equal(full.store_cpu, red.store_cpu)
    np.testing.assert_array_equal(full.store_max_mem_node,
                                  red.store_max_mem_node)
    np.testing.assert_array_equal(full.store_uniform, red.store_uniform)
    assert full.store_convergence_round() == red.store_convergence_round()
    np.testing.assert_array_equal(np.asarray(full.final_x),
                                  np.asarray(red.final_x))
    np.testing.assert_array_equal(full.final_state_bytes,
                                  red.final_state_bytes)
    for view in ("tx", "mem", "cpu", "max_mem_node", "uniform", "tx_bytes"):
        with pytest.raises(ValueError, match="object_metrics"):
            getattr(red, view)
    with pytest.raises(ValueError, match="object_metrics"):
        red.object_result(0)


def test_store_pad_to_bit_identical():
    """Object-axis padding (⊥ pad objects, masked out of results) is
    invisible: B=3 padded to any multiple matches the unpadded run."""
    topo, lat, spec = _scale_fixture()
    base = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                          quiet_rounds=Q)
    for mult in (2, 4, 5):
        padded = simulate_store("bprr", lat, topo, spec, active_rounds=T,
                                quiet_rounds=Q, pad_to=mult)
        assert padded.objects == B
        _assert_store_identical(base, padded)


def test_store_eager_validation():
    topo = topology.partial_mesh(N, 4)
    lat = GSet(universe=N * T).lattice
    # x0 leading axis != objects: rejected at StoreSpec construction
    with pytest.raises(ValueError, match="leading"):
        StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS),
                  x0=jnp.zeros((B + 1, N, N * T), jnp.bool_))
    # x0 with the right leading axis but wrong node/universe extents:
    # rejected by simulate_store before anything compiles
    spec = StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS),
                     x0=jnp.zeros((B, N + 1, N * T), jnp.bool_))
    with pytest.raises(ValueError, match=r"nodes"):
        simulate_store("bprr", lat, topo, spec, active_rounds=T)
    # op_fn emitting wrongly-shaped deltas: caught by eval_shape with an
    # actionable message, not a deep scan trace error
    bad_shape = StoreSpec(objects=B, op_fn=lambda x, t: x[:, :1])
    with pytest.raises(ValueError, match="op_fn"):
        simulate_store("bprr", lat, topo, bad_shape, active_rounds=T)
    # op_fn emitting the wrong tree structure
    bad_tree = StoreSpec(objects=B, op_fn=lambda x, t: (x, x))
    with pytest.raises(ValueError, match="op_fn"):
        simulate_store("bprr", lat, topo, bad_tree, active_rounds=T)
    with pytest.raises(ValueError, match="chunk_rounds"):
        simulate_store("bprr", lat, topo,
                       StoreSpec(objects=B, op_fn=gset_sweep_op(SEEDS)),
                       active_rounds=T, chunk_rounds=0)


# -- mixed-rank weighted accounting -------------------------------------------

def _scalar_max_lattice() -> Lattice:
    """Rank-0 max-register: its irreducible mask has NO universe axis, so
    in a product with a map lattice the wsize weights must broadcast per
    leaf (a single max-rank reshape would misalign here)."""

    def wsize(a, w):
        m = a > 0
        return m * align_weights(w, m)

    return Lattice(
        name="maxreg",
        bottom=lambda: jnp.zeros((), jnp.int32),
        join=jnp.maximum,
        leq=lambda a, b: a <= b,
        delta=lambda a, b: jnp.where(a > b, a, jnp.zeros_like(a)),
        size=lambda a: (a > 0).astype(jnp.int32),
        is_bottom=lambda a: a == 0,
        irreducible_mask=lambda a: a > 0,
        novel_mask=lambda a, b: (a > 0) & (a > b),
        wsize=wsize,
    )


def test_wsize_mixed_rank_batch_weights():
    """Per-object BatchWeights on a product of a [U]-map and a rank-0
    register: every leaf aligns the [B] weights against its own rank."""
    lat = product("mixed", (GSet(universe=4).lattice, _scalar_max_lattice()))
    x = (jnp.asarray([[True, False, True, True],
                      [False, False, True, False]]),
         jnp.asarray([5, 0]))
    got = np.asarray(lat.wsize(x, BatchWeights(jnp.asarray([2.0, 7.0]))))
    # object 0: 3 set slots + 1 register = 4 irreducibles at 2.0 each
    # object 1: 1 set slot + bottom register = 1 irreducible at 7.0
    np.testing.assert_array_equal(got, [8.0, 7.0])


def test_wsize_mixed_rank_laws():
    lat = product("mixed", (GSet(universe=4).lattice, _scalar_max_lattice()))
    x = (jnp.asarray([[True, True, False, True],
                      [False, False, False, False]]),
         jnp.asarray([3, 9]))
    # unit weights reduce to size, batched or plain
    np.testing.assert_array_equal(
        np.asarray(lat.wsize(x, BatchWeights(jnp.ones(2)))),
        np.asarray(lat.size(x)))
    np.testing.assert_array_equal(np.asarray(lat.wsize(x, 1)),
                                  np.asarray(lat.size(x)))
    # batch weights above the leaf rank are rejected, not broadcast wrong
    with pytest.raises(ValueError, match="rank"):
        lat.wsize(x, BatchWeights(jnp.ones((2, 1, 1))))


def test_store_mixed_rank_weighted_accounting():
    """End-to-end: a store over a mixed-rank product lattice prices its
    weighted final-state bytes per object (the single-reshape approach
    crashes here — the register leaf has no universe axis)."""
    topo = topology.ring(3)
    lat = product("mixed", (GSet(universe=6).lattice,
                            _scalar_max_lattice()))

    def op_fn(x, t):
        s, r = x
        b = s.shape[0]
        ds = jnp.zeros_like(s).at[:, 0, 2].set(~s[:, 0, 2])
        dr = jnp.where(t == 0,
                       jnp.arange(1, b + 1, dtype=r.dtype)[:, None] *
                       jnp.ones_like(r[:1]), jnp.zeros_like(r))
        return (ds, dr)

    w = np.asarray([10.0, 100.0])
    spec = StoreSpec(objects=2, op_fn=op_fn, weights=w)
    res = simulate_store("bprr", lat, topo, spec, active_rounds=2,
                         quiet_rounds=4)
    # each object converged to: 1 set element + 1 non-bottom register on
    # every node => 2 irreducibles priced at w[b]
    np.testing.assert_array_equal(res.final_state_bytes,
                                  np.broadcast_to(w[:, None] * 2, (2, 3)))


# -- workloads.py properties --------------------------------------------------

def _specs(draw):
    objects = draw(st.integers(1, 40))
    nodes = draw(st.integers(1, 6))
    rounds = draw(st.integers(1, 8))
    ops = draw(st.integers(1, 5))
    dist = draw(st.sampled_from(W.DISTS))
    return W.WorkloadSpec(
        objects=objects, nodes=nodes, rounds=rounds, ops_per_node=ops,
        dist=dist,
        zipf=draw(st.floats(0.0, 3.0, allow_nan=False)),
        hot_frac=draw(st.floats(0.05, 1.0, allow_nan=False)),
        hot_mass=draw(st.floats(0.0, 1.0, allow_nan=False)),
        seed=draw(st.integers(0, 2 ** 16)))


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_object_probs_normalize(data):
    spec = _specs(data.draw)
    p = spec.object_probs()
    assert p.shape == (spec.objects,)
    assert (p >= 0).all()
    assert abs(p.sum() - 1.0) < 1e-9


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_streams_seed_deterministic(data):
    spec = _specs(data.draw)
    t1, k1 = spec.streams()
    t2, k2 = spec.streams()
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(k1, k2)
    np.testing.assert_array_equal(spec.update_counts(), spec.update_counts())


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_update_counts_match_reference_loop(data):
    """The vectorized np.add.at table equals the naive python loop (the
    pre-store fig11 implementation)."""
    spec = _specs(data.draw)
    targets, kinds = spec.streams()
    per_kind = np.asarray([k.updates for k in spec.mix])
    ref = np.zeros((spec.rounds, spec.nodes, spec.objects), np.int32)
    for t in range(spec.rounds):
        for n in range(spec.nodes):
            for o, k in zip(targets[t, n], kinds[t, n]):
                ref[t, n, o] += per_kind[k]
    np.testing.assert_array_equal(spec.update_counts(), ref)


def test_op_mix_marginals_match_spec():
    """Empirical op-kind frequencies converge to the mix probabilities
    (4σ binomial bound on a 48k-op stream)."""
    spec = W.retwis(objects=50, nodes=40, rounds=40, ops_per_node=30,
                    zipf=1.0, seed=3)
    _, kinds = spec.streams()
    n = kinds.size
    for i, k in enumerate(spec.mix):
        freq = (kinds == i).mean()
        tol = 4 * np.sqrt(k.prob * (1 - k.prob) / n)
        assert abs(freq - k.prob) < tol, (k.name, freq, k.prob)


def test_zipf_contention_orders_objects():
    """Higher zipf ⇒ more probability mass on low-rank objects."""
    lo = W.retwis(100, 4, 4, 4, zipf=0.5).object_probs()
    hi = W.retwis(100, 4, 4, 4, zipf=1.5).object_probs()
    assert hi[0] > lo[0]
    assert hi[:10].sum() > lo[:10].sum()
    assert (np.diff(hi) <= 0).all()           # monotone in rank


def test_hotset_distribution():
    spec = W.WorkloadSpec(objects=100, nodes=2, rounds=2, dist="hotset",
                          hot_frac=0.1, hot_mass=0.9)
    p = spec.object_probs()
    assert abs(p[:10].sum() - 0.9) < 1e-9
    assert abs(p.sum() - 1.0) < 1e-9


def test_workload_spec_validation():
    with pytest.raises(ValueError):
        W.WorkloadSpec(objects=0, nodes=1, rounds=1)
    with pytest.raises(ValueError):
        W.WorkloadSpec(objects=1, nodes=1, rounds=1, dist="pareto")
    with pytest.raises(ValueError):
        W.WorkloadSpec(objects=1, nodes=1, rounds=1,
                       mix=(W.OpKind("bad", -0.5),))


def test_versioned_slot_cell_op_matches_batched():
    """The per-object loop baseline op is cell b of the batched store op."""
    slots = 8
    spec = W.retwis(objects=5, nodes=4, rounds=6, ops_per_node=3, zipf=1.0)
    counts = spec.update_counts()
    batched = W.versioned_slot_op(counts, slots)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 4, size=(5, 4, slots)), jnp.int32)
    for t in range(spec.rounds):
        d = batched(x, jnp.asarray(t))
        for b in range(5):
            db = W.versioned_slot_cell_op(counts, b, slots)(
                x[b], jnp.asarray(t))
            np.testing.assert_array_equal(np.asarray(d[b]), np.asarray(db))


def test_table1_builders_match_legacy_streams():
    """common.py's Table I workloads delegate here — the streams must be
    the canonical ones (seed 0 = identity permutation)."""
    op = W.gset_unique_op(4, 3)
    d0 = np.asarray(op(None, jnp.asarray(1)))
    assert d0.sum() == 4 and d0[2, 2 * 3 + 1]
    sweep = W.gset_unique_sweep_op(4, 3, (0,))
    ds = np.asarray(sweep(jnp.zeros((2, 4, 12), bool), jnp.asarray(1)))
    np.testing.assert_array_equal(ds[0], d0)
    np.testing.assert_array_equal(ds[1], d0)
    blocks = W.gmap_key_blocks(3, 30, 10)
    assert blocks.sum(axis=1).tolist() == [1, 1, 1]
    assert not (blocks.sum(axis=0) > 1).any()          # disjoint


# -- the store's program cache (DESIGN.md §16) ---------------------------------

CB, CN, CSLOTS, CACTIVE, CTOTAL = 4, 6, 8, 3, 5


def _cache_counts(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 3, (CACTIVE, CN, CB)).astype(np.int32)


def _relabelled(topo):
    """The same graph with its nodes renumbered: the topology's name and
    shapes, other neighbour tables."""
    adj = np.zeros((CN, CN), bool)
    for i, row in enumerate(topo.neighbor_lists()):
        adj[i, row] = True
    perm = np.roll(np.arange(CN), 1)
    perm[[0, 1]] = perm[[1, 0]]
    return topology._from_adj(topo.name, adj[np.ix_(perm, perm)])


class _CacheStore:
    """A Retwis-shaped store small enough for the CPU, whose lattice and
    topology objects live as long as the fixture, so that calls which
    change no key field share them."""

    def __init__(self):
        self.lat = MapLattice(CSLOTS, vl.max_int(), "slots").build()
        self.topo = topology.partial_mesh(CN, 2)

    def __call__(self, seed=0, active_rounds=CACTIVE, relabel=False,
                 rebuild_lattice=False, engine="reference", layout="rows",
                 chunk_rounds=2, telemetry=False, faults=False,
                 uncached=False, **kw):
        topo = _relabelled(self.topo) if relabel else self.topo
        lat = MapLattice(CSLOTS, vl.max_int(), "slots").build() \
            if rebuild_lattice else self.lat
        op = W.versioned_slot_op(_cache_counts(seed), CSLOTS)
        if uncached:
            # a plain closure: the table is a constant of a program built
            # for this call alone
            def op(x, t, _op=op):
                return _op(x, t)
        spec = StoreSpec(
            objects=CB, op_fn=op, weights=np.arange(1.0, CB + 1),
            faults=FaultSchedule.bernoulli(topo, CTOTAL, 0.3, seed=4)
            if faults else None)
        return simulate_store(
            "bprr", lat, topo, spec, active_rounds, CTOTAL - active_rounds,
            engine=engine, layout=layout, chunk_rounds=chunk_rounds,
            track_convergence=True,
            telemetry=TelemetrySpec() if telemetry else None, **kw)


def _assert_call_identical(a, b):
    for f in ("final_x", "tx", "mem", "cpu", "max_mem_node", "uniform",
              "tx_bytes"):
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(x, y, err_msg=f)


# one field of the second call's key changed (or, for "other_table", only
# the count table's contents: the one case that must hit)
CACHE_CASES = {
    "other_table": dict(seed=1),
    "active_rounds": dict(active_rounds=CACTIVE - 1),
    "topology_neighbours": dict(relabel=True),
    "lattice": dict(rebuild_lattice=True),
    "engine": dict(engine="fused"),
    "layout": dict(layout="grid"),
    "chunk_rounds": dict(chunk_rounds=3),
    "telemetry": dict(telemetry=True),
    "faults": dict(faults=True),
}


@pytest.mark.parametrize("case", sorted(CACHE_CASES))
def test_store_program_compiled_once_per_key(case):
    """A second call with another count table of the same shape reuses
    the first call's chunk program; a call that differs in any one key
    field compiles its own. Every call is bit-identical to the run of a
    program built for it alone (the table a constant, as a closure)."""
    store = _CacheStore()
    clear_program_cache()
    first = store()
    log = TraceLog()
    second = store(trace=log, **CACHE_CASES[case])
    info = program_cache_info()
    hit = case == "other_table"
    assert (info.hits, info.misses) == ((1, 1) if hit else (0, 2))
    (build,) = [e for e in log.events if e["name"] == "store_build"]
    assert build["args"]["program"] == ("hit" if hit else "miss")
    _assert_call_identical(first, store(uncached=True))
    _assert_call_identical(second,
                           store(uncached=True, **CACHE_CASES[case]))
    if hit:
        assert not np.array_equal(first.tx, second.tx)   # other tables


def test_cached_store_resume_bit_identical(tmp_path):
    """A run killed after its first checkpoint and resumed, both on the
    program an earlier call compiled, equals the uninterrupted run."""
    store = _CacheStore()
    clear_program_cache()
    full = store()
    with pytest.raises(KeyboardInterrupt):
        store(checkpoint=_KilledAfterSaves(tmp_path, die_after=1))
    spec = StoreSpec(objects=CB,
                     op_fn=W.versioned_slot_op(_cache_counts(0), CSLOTS),
                     weights=np.arange(1.0, CB + 1))
    res = resume_store("bprr", store.lat, store.topo, spec, CACTIVE,
                       CTOTAL - CACTIVE, checkpoint=tmp_path,
                       track_convergence=True)
    assert program_cache_info()[:2] == (2, 1)      # hits, misses
    _assert_call_identical(full, res)
    _assert_call_identical(full, store(uncached=True))


def test_store_program_cache_holds_no_operand():
    """After a call, the kept program does not hold the op stream's count
    table: it dies with the op stream. The cache has a fixed bound."""
    store = _CacheStore()
    clear_program_cache()
    op = W.versioned_slot_op(_cache_counts(0), CSLOTS)
    table = weakref.ref(op.operands[0])
    res = simulate_store("bprr", store.lat, store.topo,
                         StoreSpec(objects=CB, op_fn=op), CACTIVE,
                         CTOTAL - CACTIVE, chunk_rounds=2)
    assert program_cache_info().size == 1
    del op, res
    gc.collect()
    assert table() is None
    assert program_cache_info().maxsize == 8


def test_program_cache_evicts_least_recently_used():
    built = []
    cache = _ProgramCache(maxsize=2)

    def build(k):
        return lambda: built.append(k) or k

    assert cache.get("a", build("a")) == ("a", "miss")
    assert cache.get("b", build("b")) == ("b", "miss")
    assert cache.get("a", build("a")) == ("a", "hit")
    assert cache.get("c", build("c")) == ("c", "miss")    # evicts b
    assert cache.get("b", build("b")) == ("b", "miss")
    assert cache.get(None, build("x")) == ("x", "miss")   # never kept
    assert built == ["a", "b", "c", "b", "x"]
    assert cache.info() == (1, 5, 2, 2)


# -- LWW maps: the megakernel's lex kind and the LWW write stream ------------

LB, LN, LK, LW, LACTIVE, LQUIET = 3, 6, 40, 12, 4, 5


def _lww_tables(seed, width=1):
    """[T, N, W] (is_update, object, key) tables and the value table
    ([T, N, W] for one value plane, else [T, N, W, width], of any int32);
    few keys, so a node often writes one key twice in a round."""
    rng = np.random.default_rng(seed)
    shape = (LACTIVE, LN, LW)
    vshape = shape if width == 1 else shape + (width,)
    return (rng.integers(0, 2, shape), rng.integers(0, LB, shape),
            rng.integers(0, 6, shape),
            rng.integers(-2**31, 2**31 - 1, vshape) if width > 1
            else rng.integers(0, 2**31 - 1, shape))


def _lww_spec(seed=0, x0=True, width=1):
    from repro.core import LWWMap

    lat = LWWMap(LK, width).lattice
    start = None
    if x0:
        vals = np.random.default_rng(seed + 100).integers(
            0, 50, (width, LB, 1, LK))
        start = (jnp.ones((LB, LN, LK), jnp.int32),) + tuple(
            jnp.asarray(np.broadcast_to(v, (LB, LN, LK)), jnp.int32)
            for v in vals)
    return lat, StoreSpec(objects=LB,
                          op_fn=W.lww_write_op(_lww_tables(seed, width), LK),
                          weights=np.full(LB, 100.0), x0=start)


def test_lww_write_op_newest_write_wins():
    """A node's delta in a round holds, per written key, the timestamp
    ``2 + (t·N + n)·W + w`` of its newest write and that write's value;
    reads and unwritten keys are ⊥."""
    tables = _lww_tables(3)
    op = W.lww_write_op(tables, LK)
    x = (jnp.zeros((LB, LN, LK), jnp.int32),) * 2
    for t in range(LACTIVE + 1):
        dt, dv = op(x, jnp.int32(t))
        tt = min(t, LACTIVE - 1)
        want_t = np.zeros((LB, LN, LK), np.int64)
        want_v = np.zeros((LB, LN, LK), np.int64)
        for n in range(LN):
            for w in range(LW):
                if tables[0][tt, n, w]:
                    o, k = tables[1][tt, n, w], tables[2][tt, n, w]
                    want_t[o, n, k] = 2 + (tt * LN + n) * LW + w
                    want_v[o, n, k] = tables[3][tt, n, w]
        np.testing.assert_array_equal(np.asarray(dt), want_t)
        np.testing.assert_array_equal(np.asarray(dv), want_v)
        assert dt.dtype == dv.dtype == jnp.int32


def test_lww_write_op_wide_records():
    """A record of several value planes: the newest write of a (node, key)
    carries every plane of its record, negative words included."""
    width = 3
    tables = _lww_tables(4, width)
    op = W.lww_write_op(tables, LK)
    x = (jnp.zeros((LB, LN, LK), jnp.int32),) * (1 + width)
    for t in range(LACTIVE):
        got = op(x, jnp.int32(t))
        assert len(got) == 1 + width
        want = np.zeros((1 + width, LB, LN, LK), np.int64)
        for n in range(LN):
            for w in range(LW):
                if tables[0][t, n, w]:
                    o, k = tables[1][t, n, w], tables[2][t, n, w]
                    want[0, o, n, k] = 2 + (t * LN + n) * LW + w
                    want[1:, o, n, k] = tables[3][t, n, w]
        for g, wnt in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), wnt)
    with pytest.raises(AssertionError, match="value planes"):
        op((jnp.zeros((LB, LN, LK), jnp.int32),) * 2, jnp.int32(0))


def test_lww_write_op_validation():
    t = _lww_tables(0)
    with pytest.raises(ValueError, match="four"):
        W.lww_write_op(t[:3], LK)
    with pytest.raises(ValueError, match="overflow"):
        # shapes are checked before any table is copied: a broadcast
        # table holds no memory
        W.lww_write_op((np.broadcast_to(np.int32(0), (2**12, 2**10, 2**10)),)
                       * 4, LK)
    with pytest.raises(ValueError, match="value table"):
        W.lww_write_op(t[:3] + (t[3][:, :, :2],), LK)


@pytest.mark.parametrize("algo", ["state", "classic", "bp", "rr", "bprr"])
def test_lww_store_mega_bit_identical(algo):
    """An LWW-map store from a loaded start state runs the megakernel's
    lex kind under ``mega``: final states and every per-object per-round
    metric equal the reference engine's, chunked or not."""
    lat, spec = _lww_spec()
    topo = topology.partial_mesh(LN, 4)
    want = simulate_store(algo, lat, topo, spec, LACTIVE, LQUIET,
                          engine="reference", track_convergence=True)
    got = simulate_store(algo, lat, topo, spec, LACTIVE, LQUIET,
                         engine="mega", chunk_rounds=3,
                         track_convergence=True)
    for a, b in zip(want.final_x, got.final_x):
        np.testing.assert_array_equal(a, b)
    for f in ("tx", "mem", "cpu", "max_mem_node", "uniform", "tx_bytes"):
        np.testing.assert_array_equal(getattr(want, f), getattr(got, f),
                                      err_msg=f)
    assert want.tx.sum() > 0 and got.uniform[:, -1].all()


@pytest.mark.parametrize("algo", ["state", "classic", "bp", "rr", "bprr"])
def test_wide_lww_store_mega_bit_identical(algo):
    """LWW maps whose keys hold records of three value planes run the lex
    kernel under ``mega`` bit-identically to the reference engine."""
    lat, spec = _lww_spec(seed=5, width=3)
    topo = topology.partial_mesh(LN, 4)
    want = simulate_store(algo, lat, topo, spec, LACTIVE, LQUIET,
                          engine="reference", track_convergence=True)
    got = simulate_store(algo, lat, topo, spec, LACTIVE, LQUIET,
                         engine="mega", chunk_rounds=3,
                         track_convergence=True)
    assert len(got.final_x) == 4
    for a, b in zip(want.final_x, got.final_x):
        np.testing.assert_array_equal(a, b)
    for f in ("tx", "mem", "cpu", "max_mem_node", "uniform", "tx_bytes"):
        np.testing.assert_array_equal(getattr(want, f), getattr(got, f),
                                      err_msg=f)
    assert want.tx.sum() > 0 and got.uniform[:, -1].all()


def test_chunked_store_keeps_the_callers_x0():
    """The chunk program donates its input carry; a spec's x0 serves call
    after call."""
    lat, spec = _lww_spec()
    topo = topology.partial_mesh(LN, 4)
    runs = [simulate_store("bprr", lat, topo, spec, LACTIVE, LQUIET,
                           engine="mega", chunk_rounds=2) for _ in range(2)]
    assert not any(a.is_deleted() for a in spec.x0)
    for a, b in zip(runs[0].final_x, runs[1].final_x):
        np.testing.assert_array_equal(a, b)


def test_lww_write_op_traces_in_int32_under_the_metric_context():
    """The simulator traces the op stream under x64: the LWW write stream
    pins its indices, clock and planes to int32, so its jaxpr holds no
    64-bit integer (emulated on the chip)."""
    import jax

    from repro.sync.simulator import metric_context

    def dtypes(jx):
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                if hasattr(v, "aval"):
                    yield v.aval.dtype
        for sub in jax.core.subjaxprs(jx):
            yield from dtypes(sub)

    for width in (1, 3):
        op = W.lww_write_op(_lww_tables(1, width), LK)
        x = (jnp.zeros((LB, LN, LK), jnp.int32),) * (1 + width)
        with metric_context(True):
            jaxpr = jax.make_jaxpr(op.apply)(op.operands, x, jnp.int32(2))
        wide = {str(d) for d in dtypes(jaxpr.jaxpr)} & {"int64", "uint64"}
        assert not wide, (width, wide)
