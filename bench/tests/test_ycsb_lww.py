"""The YCSB workload A deployment over replicated LWW maps
(``deployments/ycsb_lww.py``) at a size the CPU runs in seconds: it runs
through the unchanged harness and is correct, planted faults and its
controls are caught, its schedule is workload A's, and its store refuses a
program that would not run the configuration's engine."""

import time

import jax
import numpy as np
import pytest

from bench import check, control, run, spec

SEED = 2**31 + 77


@pytest.fixture
def small_cell():
    """``ycsb-a-lww.workloada`` cut to 4 objects of 250 keys (100 records
    of 10 fields) at 8 nodes, 6 rounds of which 3 active, 40 operations a
    node a round, and fields of 12 B (3 words: the interpreted kernel's
    program grows with the planes; the cell's 25 are compiled on the
    chip)."""
    full = spec.cell("ycsb-a-lww.workloada")

    def make(algorithm="bprr", ops=40):
        config = dict(full.config, objects=4, records=100, keys=250,
                      fieldlength=12, nodes=8, rounds=6, chunk_rounds=3,
                      algorithm=algorithm)
        traffic = dict(full.traffic, active_rounds=3, ops_per_node=ops)
        return spec.Cell(name="small", chips=1, config=config,
                         traffic=traffic, end_to_end=full.end_to_end,
                         per_layer=full.per_layer)

    return make


def execute(cell, seed=SEED):
    res, checks = run.execute(cell, seed, 0.1, 0, jax.devices(),
                              time.perf_counter())
    return res, {k: v["value"] for k, v in checks.items()}


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
def test_small_cell_runs_correct(small_cell, algorithm):
    cell = small_cell(algorithm)
    res, numbers = execute(cell)
    assert res["correct"], numbers
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert all(v == 0 for v in numbers.values())
    dep = spec.deployment(cell)
    sched = dep.schedule(cell.config, cell.traffic, SEED)
    ref = dep.reference(cell.config, sched, cell.rounds)
    assert ref["tx"].sum() > 0 and ref["uniform"][:, -1].all()
    # the writes are real: some key at some replica is past the load
    assert (ref["final_x"][0] > 1).any()


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


def _value_flipped(x):
    """The first value word of one key at one replica flipped: its
    timestamp stands."""
    i = np.unravel_index(np.argmax(x[0]), x[0].shape)
    x[1][i] ^= 1
    return x


def _last_word_flipped(x):
    """The last of a field's words flipped at one key of one replica."""
    assert len(x) == 4
    i = np.unravel_index(np.argmax(x[0]), x[0].shape)
    x[-1][i] ^= 1 << 30
    return x


def _write_dropped(x):
    """The newest write of object 0 dropped at every replica: its key back
    at the loaded timestamp 1."""
    ts = x[0]
    k = np.unravel_index(np.argmax(ts[0]), ts[0].shape)[1]
    ts[0, :, k] = 1
    return x


@pytest.mark.parametrize("fault, number", [
    (_value_flipped, "objects_state_mismatch"),
    (_last_word_flipped, "objects_state_mismatch"),
    (_write_dropped, "acked_updates_missing")])
def test_planted_fault_is_caught(small_cell, monkeypatch, fault, number):
    _planted(fault)(monkeypatch)
    res, numbers = execute(small_cell())
    assert not res["correct"], numbers
    assert numbers[number] > 0


@pytest.mark.parametrize("algorithm", ["bprr", "classic"])
def test_controls_are_not_correct(small_cell, algorithm):
    got = control.readings(small_cell(algorithm), 7)
    assert set(got) == {"lossy", "unsent"}
    for name, numbers in got.items():
        assert not check.within(numbers), (name, numbers)
    assert got["unsent"]["acked_updates_missing"] > 0


def test_schedule_is_workload_a():
    """Half the operations update, one field each, drawn uniformly; the
    hottest records are the scrambled Zipf ranks' hashes at 0.99."""
    full = spec.cell("ycsb-a-lww.workloada")
    dep = spec.deployment(full)
    config = full.config
    traffic = dict(full.traffic, active_rounds=4)
    sched = dep.schedule(config, traffic, SEED)
    ops = sched["update"].size                       # 4 x 50 x 1,000
    assert ops == 200_000
    # update share: 0.5 within 5 standard deviations
    share = sched["update"].mean()
    assert abs(share - 0.5) < 5 * np.sqrt(0.25 / ops), share
    upd = sched["update"] != 0
    field = sched["key"] % config["fields"]
    counts = np.bincount(field[upd], minlength=10)
    expect = upd.sum() / 10
    assert np.all(np.abs(counts - expect) < 5 * np.sqrt(expect)), counts
    # record r lives in object r mod 100, its fields at (r div 100) x 10
    rec = sched["record"]
    np.testing.assert_array_equal(sched["object"], rec % 100)
    np.testing.assert_array_equal(sched["key"] // 10, rec // 100)
    # the hottest records are the hashes of Zipf ranks 0..4, each drawn as
    # often as YCSB's ZipfianGenerator draws it: ranks 0 and 1 with
    # probability (rank + 1)^-0.99 / zeta(10^10, 0.99), the others by its
    # closed-form inverse (within 25% of Zipf at these ranks)
    freq = np.bincount(rec.ravel(), minlength=config["records"]) / ops
    ranks = (dep.fnvhash64(np.arange(5))
             % np.uint64(config["records"])).astype(np.int64)
    theta, items = 0.99, dep.ITEM_COUNT + 1
    zipf = 1.0 / (np.arange(1, 6) ** theta * dep.ZETAN)
    eta = (1 - (2 / items) ** (1 - theta)) / (1 - (1 + 0.5 ** theta)
                                              / dep.ZETAN)
    u = ((np.arange(2, 6) / items) ** (1 - theta) + eta - 1) / eta
    want = np.concatenate([zipf[:2], u[1:] - np.maximum(
        u[:-1], (1 + 0.5 ** theta) / dep.ZETAN)])
    assert np.all(np.abs(want / zipf - 1) < 0.25)
    got = freq[ranks]
    assert np.all(np.abs(got - want) < 5 * np.sqrt(want / ops)), (got, want)
    assert list(np.argsort(-freq)[:3]) == list(ranks[:3])


def test_fnvhash64_is_fnv1a_over_eight_bytes():
    """YCSB's ``Utils.fnvhash64``, checked against Python integers."""
    dep = spec.deployment(spec.cell("ycsb-a-lww.workloada"))

    def plain(v):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (v & 0xFF)) * 1099511628211) % 2**64
            v >>= 8
        h = h - 2**64 if h >= 2**63 else h
        return abs(h)

    vals = [0, 1, 2, 255, 256, 123_456_789, 10_000_000_000]
    np.testing.assert_array_equal(
        dep.fnvhash64(np.asarray(vals)),
        np.asarray([plain(v) for v in vals], np.uint64))


def test_timestamps_of_the_cell_fit_int32():
    cell = spec.cell("ycsb-a-lww.workloada")
    c, tr = cell.config, cell.traffic
    assert 2 + tr["active_rounds"] * c["nodes"] * tr["ops_per_node"] \
        < 2**31 - 1
    assert c["keys"] == c["records"] // c["objects"] * c["fields"]


def test_round_bytes_of_the_cell():
    """13 records of 26 [100, 50, 1,280] int32 planes a round (timestamp
    and 25 words of a 100 B field): delta, state and five buffers read,
    state and five buffers written."""
    cell = spec.cell("ycsb-a-lww.workloada")
    assert spec.deployment(cell).round_bytes(cell.config) == \
        13 * 26 * 100 * 50 * 1_280 * 4 == 8_652_800_000


def test_records_are_held_whole():
    """A key holds its field's 100 B as 25 words on the device: the load
    records, the op tables' values and the store's start state carry them,
    and the maps' lattice orders 26-plane records."""
    cell = spec.cell("ycsb-a-lww.workloada")
    dep = spec.deployment(cell)
    c = dict(cell.config, objects=4, records=100, keys=250, nodes=8)
    sched = dep.schedule(c, dict(cell.traffic, active_rounds=2,
                                 ops_per_node=8), SEED)
    assert sched["load"].shape == (4, 250, 25)
    assert sched["value"].shape == (2, 8, 8, 25)
    assert (sched["value"] < 0).any() and (sched["value"] > 0).any()
    lattice, _, store_spec = dep.store(c, sched)
    assert len(store_spec.x0) == 26
    np.testing.assert_array_equal(np.asarray(store_spec.x0[25][:, 3]),
                                  sched["load"][..., 24])
    assert len(lattice.bottom()) == 26


def test_store_refuses_a_fallback_engine(small_cell, monkeypatch):
    """Where the program would not run LWW maps on the configuration's
    engine, the store raises before anything runs."""
    from repro.sync import engine

    cell = small_cell()
    dep = spec.deployment(cell)
    sched = dep.schedule(cell.config, cell.traffic, 3)
    monkeypatch.setattr(engine, "resolve", lambda *a, **kw: "reference")
    with pytest.raises(RuntimeError, match="reference"):
        dep.store(cell.config, sched)
