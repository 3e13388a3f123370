"""Kernel-engine equivalence: the fused Pallas chain AND the single-launch
megakernel must be bit-identical to the reference jnp loop (DESIGN.md
§11/§17).

For every algorithm in ALGORITHMS × every dense-kernel lattice kind
(GSet bool-or, GCounter/GMap ℕ-max, BitGSet packed bitor) × topology
(mesh, tree, random connected) × kernel engine (fused, mega), results must
match the reference engine exactly: final states, per-round tx / mem /
cpu / max-node-memory, and per-node buffer counts — fault-free and under
composed fault schedules — and still converge. Lex-pair maps (LWW maps)
run the megakernel's lex kind under ``mega`` and fall back to the
reference engine under ``fused`` and in the resync modes, bit-identically
either way.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BitGSet, GCounter, GSet, LWWMap
from repro.core import LexCounter
from repro.sync import (ALGORITHMS, FaultSchedule, SyncAlgorithm, converged,
                        engine, simulate, topology)
from repro.sync.algorithms import RESYNC_ALGORITHMS

N, T, Q = 9, 8, 10
KERNEL_ENGINES = list(engine.KERNEL_ENGINES)


def gset_ops(n=N, rounds=T):
    def op_fn(x, t):
        ids = jnp.arange(n) * rounds + jnp.minimum(t, rounds - 1)
        d = jnp.zeros((n, n * rounds), jnp.bool_)
        return d.at[jnp.arange(n), ids].set(True)

    return op_fn, GSet(universe=n * rounds).lattice


def gcounter_ops(n=N):
    def op_fn(x, t):
        d = jnp.zeros((n, n), jnp.int32)
        idx = jnp.arange(n)
        return d.at[idx, idx].set(x[idx, idx] + 1)

    return op_fn, GCounter(n).lattice


def bitgset_ops(n=N, rounds=T):
    """Unique-element adds on the packed set — one new bit per node/round."""
    bg = BitGSet(universe=n * rounds)

    def op_fn(x, t):
        ids = jnp.arange(n) * rounds + jnp.minimum(t, rounds - 1)
        m = jnp.zeros((n, bg.num_words), jnp.uint32)
        m = m.at[jnp.arange(n), ids // 32].set(
            jnp.uint32(1) << (ids % 32).astype(jnp.uint32))
        return bg.add_mask_delta(x, m)

    return op_fn, bg.lattice


def lww_ops(n=N):
    """Lex-pair states: the megakernel's lex kind, the fused fallback."""
    lm = LWWMap(num_keys=n)

    def op_fn(x, t):
        ts, vals = x
        idx = jnp.arange(n)
        dt = jnp.zeros_like(ts).at[idx, idx].set(t.astype(ts.dtype) + 1)
        dv = jnp.zeros_like(vals).at[idx, idx].set(idx.astype(vals.dtype) * 3)
        return (dt, dv)

    return op_fn, lm.lattice


def wide_lww_ops(n=N, width=3):
    """LWW maps whose keys hold records of ``width`` value planes: node i
    writes keys i and i + 1 at one timestamp, so its neighbour's write of
    key i + 1 ties on the timestamp and the value planes decide."""
    lm = LWWMap(num_keys=n, width=width)

    def op_fn(x, t):
        idx = jnp.arange(n)
        out = []
        for j, c in enumerate(x):
            d = jnp.zeros_like(c)
            if j == 0:
                stamp = jnp.full((n,), t.astype(c.dtype) + 1)
                vals = (stamp, stamp)
            else:
                vals = ((idx * 3 - j).astype(c.dtype),
                        ((idx + 1) * 5 % 7 - j).astype(c.dtype))
            d = d.at[idx, idx].set(vals[0])
            d = d.at[idx, (idx + 1) % n].set(vals[1])
            out.append(d)
        return tuple(out)

    return op_fn, lm.lattice


WORKLOADS = {
    "gset": gset_ops,
    "gcounter": gcounter_ops,
    "bitgset": bitgset_ops,
    "lww": lww_ops,
}


def _run_both(algo, op_builder, topo, eng="fused", faults=None):
    op_fn, lat = op_builder()
    a = simulate(algo, lat, topo, op_fn, active_rounds=T, quiet_rounds=Q,
                 engine="reference", faults=faults)
    op_fn, lat = op_builder()
    b = simulate(algo, lat, topo, op_fn, active_rounds=T, quiet_rounds=Q,
                 engine=eng, faults=faults)
    return a, b, lat


def _assert_identical(a, b, ctx):
    fa = a.final_x if isinstance(a.final_x, (list, tuple)) else (a.final_x,)
    fb = b.final_x if isinstance(b.final_x, (list, tuple)) else (b.final_x,)
    for la, lb in zip(fa, fb):
        np.testing.assert_array_equal(la, lb, err_msg=f"{ctx}: final state")
    np.testing.assert_array_equal(a.tx, b.tx, err_msg=f"{ctx}: tx")
    np.testing.assert_array_equal(a.mem, b.mem, err_msg=f"{ctx}: mem")
    np.testing.assert_array_equal(a.cpu, b.cpu, err_msg=f"{ctx}: cpu")
    np.testing.assert_array_equal(a.max_mem_node, b.max_mem_node,
                                  err_msg=f"{ctx}: max_mem_node")


@pytest.mark.parametrize("eng", KERNEL_ENGINES)
@pytest.mark.parametrize("algo", ALGORITHMS)
@pytest.mark.parametrize("workload", ["gset", "gcounter", "bitgset"])
@pytest.mark.parametrize("topo_name", ["mesh", "tree"])
def test_kernel_engines_bit_identical(algo, workload, topo_name, eng):
    topo = topology.by_name(topo_name, N)
    a, b, lat = _run_both(algo, WORKLOADS[workload], topo, eng)
    _assert_identical(a, b, f"{workload}/{algo}/{topo_name}/{eng}")
    assert converged(lat, b.final_x)


@pytest.mark.parametrize("eng", KERNEL_ENGINES)
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_kernel_engines_bit_identical_faulted(algo, eng):
    """Composed loss + churn schedule: delivery gating (ack-masked buffer
    clears, down nodes, masked inbox slots) must match the reference
    engine exactly through both kernel paths."""
    topo = topology.partial_mesh(N, 4)
    total = T + Q
    faults = FaultSchedule.bernoulli(topo, total - 4, 0.3, seed=3).compose(
        FaultSchedule.churn(topo, total - 4, [(2, 2, 5)]))
    a, b, lat = _run_both("state" if algo == "state" else algo,
                          WORKLOADS["gset"], topo, eng, faults=faults)
    _assert_identical(a, b, f"gset/{algo}/faulted/{eng}")
    assert converged(lat, b.final_x)     # fault-free drain tail


@pytest.mark.parametrize("eng", KERNEL_ENGINES)
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_lex_lattice_falls_back_and_matches(algo, eng):
    """LWW maps run ``mega``'s lex kernel (the delta family) or fall back
    to the reference engine (``fused``, the resync modes); either way the
    final states, every per-round metric and, round by round, the states,
    buffers and buffered-element counts are the reference engine's."""
    topo = topology.partial_mesh(N, 4)
    a, b, lat = _run_both(algo, WORKLOADS["lww"], topo, eng)
    _assert_identical(a, b, f"lww/{algo}/{eng}")
    np.testing.assert_array_equal(a.uniform, b.uniform)
    assert converged(lat, b.final_x)

    op_fn, lat = lww_ops()
    algs = {e: SyncAlgorithm(name=algo, lattice=lat, topo=topo, engine=e)
            for e in ("reference", eng)}
    runs = "mega" if eng == "mega" and algo not in RESYNC_ALGORITHMS \
        else "reference"
    assert algs[eng].resolved_engine == runs
    carries = {e: al.init() for e, al in algs.items()}
    for t in range(T):
        delta = op_fn(carries["reference"].x, jnp.asarray(t))
        for e, al in algs.items():
            carries[e], _ = al.round_step(carries[e], delta)
        want, got = carries["reference"], carries[eng]
        for part in ("x", "buf", "buf_elems"):
            for w, g in zip(jax.tree.leaves(getattr(want, part)),
                            jax.tree.leaves(getattr(got, part))):
                np.testing.assert_array_equal(
                    np.asarray(w), np.asarray(g),
                    err_msg=f"{algo}/{eng}: {part} @ round {t}")


@pytest.mark.parametrize("algo", ALGORITHMS)
def test_wide_lex_lattice_mega_matches(algo):
    """LWW maps of three-plane records: ``mega`` runs the lex kernel for
    the delta family (the resync modes fall back) and equals the reference
    engine in final states and every per-round metric, with timestamp ties
    decided by the value planes."""
    topo = topology.partial_mesh(N, 4)
    a, b, lat = _run_both(algo, wide_lww_ops, topo, "mega")
    _assert_identical(a, b, f"wide-lww/{algo}")
    assert len(b.final_x) == 4
    np.testing.assert_array_equal(a.uniform, b.uniform)
    assert converged(lat, b.final_x)
    runs = "reference" if algo in RESYNC_ALGORITHMS else "mega"
    assert SyncAlgorithm(name=algo, lattice=lat, topo=topo,
                         engine="mega").resolved_engine == runs


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("algo", ["classic", "bprr"])
def test_lex_mega_random_topologies(seed, algo):
    """LWW maps on ``mega`` over random connected graphs with ragged
    degrees match the reference engine."""
    rng = np.random.default_rng(seed + 10)
    n = int(rng.integers(5, 11))
    adj = np.zeros((n, n), bool)
    for i in range(1, n):
        j = int(rng.integers(0, i))
        adj[i, j] = adj[j, i] = True
    topo = topology._from_adj(f"lexrand{seed}", adj)
    a, b, lat = _run_both(algo, lambda: lww_ops(n), topo, "mega")
    _assert_identical(a, b, f"lexrand{seed}/{algo}")
    assert converged(lat, b.final_x)


@pytest.mark.parametrize("eng", KERNEL_ENGINES)
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("algo", ALGORITHMS)
def test_kernel_engines_random_topologies(seed, algo, eng):
    """Random connected graphs with ragged degrees (padding slots exercise
    the kernels' ⊥-masked inbox and the megakernel's pad-row routes)."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 12))
    adj = np.zeros((n, n), bool)
    order = rng.permutation(n)
    for i in range(1, n):
        j = order[rng.integers(0, i)]
        adj[order[i], j] = adj[j, order[i]] = True
    for _ in range(n // 2):
        a_, b_ = rng.integers(0, n, 2)
        if a_ != b_:
            adj[a_, b_] = adj[b_, a_] = True
    topo = topology._from_adj(f"rand{seed}", adj)

    def build():
        return gset_ops(n, T)

    a, b, lat = _run_both(algo, build, topo, eng)
    _assert_identical(a, b, f"rand{seed}/{algo}/{eng}")
    assert converged(lat, b.final_x)


def test_engine_buffer_counts_identical():
    """Step-level check: carries (buffers and per-node buffered-element
    counters) match after every round for EVERY engine, not just
    end-of-run metrics."""
    topo = topology.partial_mesh(N, 4)
    op_fn, lat = gset_ops()
    algs = {
        e: SyncAlgorithm(name="bprr", lattice=lat, topo=topo, engine=e)
        for e in engine.ENGINES
    }
    carries = {e: a.init() for e, a in algs.items()}
    for t in range(6):
        delta = op_fn(carries["reference"].x, jnp.asarray(t))
        for e in engine.ENGINES:
            carries[e], _ = algs[e].round_step(carries[e], delta)
        for e in engine.KERNEL_ENGINES:
            np.testing.assert_array_equal(
                np.asarray(carries["reference"].buf),
                np.asarray(carries[e].buf), err_msg=f"{e} buf @ round {t}")
            np.testing.assert_array_equal(
                np.asarray(carries["reference"].buf_elems),
                np.asarray(carries[e].buf_elems),
                err_msg=f"{e} buf_elems @ round {t}")
            np.testing.assert_array_equal(
                np.asarray(carries["reference"].x),
                np.asarray(carries[e].x), err_msg=f"{e} x @ round {t}")


def test_engine_resolution():
    def run(eng, lat, resync=False):
        return engine.resolve(eng, lat, resync=resync)

    assert run("fused", GSet(universe=8).lattice) == "fused"
    assert run("fused", BitGSet(universe=64).lattice) == "fused"
    assert run("fused", LWWMap(num_keys=4).lattice) == "reference"
    assert run("mega", GSet(universe=8).lattice) == "mega"
    assert run("mega", BitGSet(universe=64).lattice) == "mega"
    # the megakernel runs lex maps, narrow or wide; the fused kernels,
    # which the resync modes take under mega too, do not
    assert run("mega", LWWMap(num_keys=4).lattice) == "mega"
    assert run("mega", LWWMap(num_keys=4, width=3).lattice) == "mega"
    assert run("mega", LexCounter(3).lattice) == "mega"
    assert run("mega", LWWMap(num_keys=4).lattice, resync=True) == "reference"
    assert run("mega", GSet(universe=8).lattice, resync=True) == "mega"
    assert run("reference", GSet(universe=8).lattice) == "reference"
    with pytest.raises(ValueError):
        run("warp", GSet(universe=8).lattice)
    with pytest.raises(TypeError):      # no default: the caller must say
        engine.resolve("mega", LWWMap(num_keys=4).lattice)


def test_kernel_kind_assignments():
    assert GSet(universe=8).lattice.kernel_kind == "max"
    assert GCounter(4).lattice.kernel_kind == "max"
    assert BitGSet(universe=64).lattice.kernel_kind == "bitor"
    assert LWWMap(num_keys=4).lattice.kernel_kind == "lex"
    assert LWWMap(num_keys=4, width=25).lattice.kernel_kind == "lex"
    assert LexCounter(3).lattice.kernel_kind == "lex"


@pytest.mark.parametrize("eng", KERNEL_ENGINES)
def test_kernel_loo_matches_naive(eng):
    """Kernelized leave-one-out sends == the O(P²) naive fold."""
    topo = topology.partial_mesh(N, 4)
    op_fn, lat = gset_ops()
    a = simulate("bprr", lat, topo, op_fn, active_rounds=T, quiet_rounds=Q,
                 engine=eng)
    b = simulate("bprr", lat, topo, op_fn, active_rounds=T, quiet_rounds=Q,
                 engine="reference", loo="naive")
    np.testing.assert_array_equal(a.final_x, b.final_x)
    np.testing.assert_array_equal(a.tx, b.tx)
