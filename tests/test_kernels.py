"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

All kernels are integer/boolean lattice ops — comparisons are exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref

SHAPES = [(64,), (1000,), (7, 333), (512, 1024), (3, 5, 129), (2048, 2048)]
DTYPES = [jnp.int32, jnp.uint32, jnp.int8]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_join_max_sweep(shape, dtype, rng):
    a = jnp.asarray(rng.integers(0, 100, size=shape), dtype)
    b = jnp.asarray(rng.integers(0, 100, size=shape), dtype)
    np.testing.assert_array_equal(ops.join(a, b), ref.join(a, b))


@pytest.mark.parametrize("shape", SHAPES)
def test_join_bitor_sweep(shape, rng):
    a = jnp.asarray(rng.integers(0, 2**31, size=shape), jnp.uint32)
    b = jnp.asarray(rng.integers(0, 2**31, size=shape), jnp.uint32)
    np.testing.assert_array_equal(
        ops.join(a, b, kind="bitor"), ref.join(a, b, kind="bitor"))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("kind", ["max", "bitor"])
def test_delta_extract_sweep(shape, kind, rng):
    dt = jnp.uint32 if kind == "bitor" else jnp.int32
    hi = 2**31 if kind == "bitor" else 8
    d = jnp.asarray(rng.integers(0, hi, size=shape), dt)
    x = jnp.asarray(rng.integers(0, hi, size=shape), dt)
    s, xj, cnt = ops.delta_extract(d, x, kind=kind)
    rs, rxj, rcnt = ref.delta_extract(d, x, kind=kind)
    np.testing.assert_array_equal(s, rs)
    np.testing.assert_array_equal(xj, rxj)
    assert int(cnt) == int(rcnt)


@pytest.mark.parametrize("k", [2, 3, 5, 9])
@pytest.mark.parametrize("n", [100, 4096])
def test_buffer_fold_sweep(k, n, rng):
    buf = jnp.asarray(rng.integers(0, 50, size=(k, n)), jnp.int32)
    np.testing.assert_array_equal(ops.buffer_fold(buf), ref.buffer_fold(buf))


@pytest.mark.parametrize("k", [2, 4])
def test_buffer_fold_bitor(k, rng):
    buf = jnp.asarray(rng.integers(0, 2**31, size=(k, 777)), jnp.uint32)
    np.testing.assert_array_equal(
        ops.buffer_fold(buf, kind="bitor"),
        ref.buffer_fold(buf, kind="bitor"))


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 300),
    seed=st.integers(0, 2**31 - 1),
)
def test_delta_extract_property(n, seed):
    """Fused kernel Δ agrees with the lattice-level optimal delta."""
    rng = np.random.default_rng(seed)
    d = jnp.asarray(rng.integers(0, 6, size=(n,)), jnp.int32)
    x = jnp.asarray(rng.integers(0, 6, size=(n,)), jnp.int32)
    s, xj, cnt = ops.delta_extract(d, x)
    # Δ(d,x) ⊔ x == d ⊔ x
    np.testing.assert_array_equal(jnp.maximum(s, x), jnp.maximum(d, x))
    np.testing.assert_array_equal(xj, jnp.maximum(d, x))
    assert int(cnt) == int(jnp.sum(d > x))


@settings(max_examples=25, deadline=None)
@given(universe=st.integers(1, 200), seed=st.integers(0, 2**31 - 1))
def test_bitpacked_gset_roundtrip_and_join(universe, seed):
    """Bit-packed joins == boolean joins (8× wire/memory format)."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.integers(0, 2, size=(universe,)).astype(bool))
    b = jnp.asarray(rng.integers(0, 2, size=(universe,)).astype(bool))
    pa, pb = ops.pack_bits(a), ops.pack_bits(b)
    joined = ops.join(pa, pb, kind="bitor")
    np.testing.assert_array_equal(
        ops.unpack_bits(joined, universe), jnp.logical_or(a, b))
    s, _, cnt = ops.delta_extract(pa, pb, kind="bitor")
    np.testing.assert_array_equal(
        ops.unpack_bits(s, universe), a & ~b)
    assert int(cnt) == int(jnp.sum(a & ~b))


# -- sync-round megakernel vs whole-round oracle (DESIGN.md §17) --------------

def _mega_case(rng, b, n, u, p, k, kind, per_origin, extracts, topo,
               block=None, width=1):
    dtype = jnp.uint32 if kind == "bitor" else jnp.int32
    hi = {"bitor": 2**31, "max": 50, "lex": 4}[kind]

    def mk(*s):
        one = lambda lo=0: jnp.asarray(rng.integers(lo, hi, size=s), dtype)
        # lex: few timestamps and values, so equal ones with other values
        # (ties decided by a later plane) and ⊥ points are common; wide
        # records' value planes hold negative words too
        lo = -2 if width > 1 else 0
        return ((one(),) + tuple(one(lo) for _ in range(width))
                if kind == "lex" else one())

    delta, x = mk(b, n, u), mk(b, n, u)
    buf = mk(k, b, n, u) if k else None
    active = jnp.asarray(
        rng.integers(0, 2, size=(b, n, p)), jnp.int32) * topo.mask
    delivered = jnp.asarray(rng.integers(0, 2, size=(b, n)), jnp.int32) \
        if k else None
    kw = dict(nbrs=topo.nbrs, rev=topo.rev, kind=kind,
              per_origin=per_origin, extracts=extracts)
    got = ops.sync_round(delta, x, buf, active, delivered, block=block, **kw)
    want = ref.sync_round(delta, x, buf, active, delivered, **kw)
    names = ("x'", "buf'", "inbox", "dsz_op", "xsz", "ssend", "cnt", "dsz")
    for nm, g, w in zip(names, got, want):
        if w is None:
            assert g is None, nm
            continue
        gl, wl = jax.tree.leaves(g), jax.tree.leaves(w)
        assert len(gl) == len(wl) == (1 + width if kind == "lex" and nm in
                                      names[:3] else 1), nm
        for a, c in zip(gl, wl):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c),
                                          err_msg=nm)


# (k, per_origin, extracts) per algorithm flavor; k is in units of P+1 for
# the per-origin buffers (resolved inside the test).
MEGA_FLAVORS = {
    "state": (0, False, False),
    "classic": (1, False, False),
    "bp": ("P+1", True, False),
    "rr": (1, False, True),
    "bprr": ("P+1", True, True),
}


@pytest.mark.parametrize("kind", ["max", "bitor", "lex"])
@pytest.mark.parametrize("flavor", sorted(MEGA_FLAVORS))
@pytest.mark.parametrize("b", [1, 3])
def test_sync_round_megakernel_vs_oracle(kind, flavor, b, rng):
    from repro.sync import topology

    topo = topology.partial_mesh(9, 4)
    p = topo.max_degree
    k, per_origin, extracts = MEGA_FLAVORS[flavor]
    k = p + 1 if k == "P+1" else k
    _mega_case(rng, b, topo.num_nodes, 333, p, k, kind, per_origin,
               extracts, topo)


@pytest.mark.parametrize("flavor", sorted(MEGA_FLAVORS))
@pytest.mark.parametrize("seed", [0, 1])
def test_sync_round_lex_ragged_topology(flavor, seed):
    """The lex kind over a random connected graph with ragged degrees: the
    padding slots route to (0, 0) and are masked off in VMEM."""
    from repro.sync import topology

    rng = np.random.default_rng(seed)
    n = int(rng.integers(5, 10))
    adj = np.zeros((n, n), bool)
    for i in range(1, n):
        j = int(rng.integers(0, i))
        adj[i, j] = adj[j, i] = True
    topo = topology._from_adj(f"ragged{seed}", adj)
    p = topo.max_degree
    k, per_origin, extracts = MEGA_FLAVORS[flavor]
    k = p + 1 if k == "P+1" else k
    _mega_case(rng, 2, n, 200, p, k, "lex", per_origin, extracts, topo)


@pytest.mark.parametrize("flavor", ["classic", "bprr"])
def test_sync_round_lex_lane_tiles(flavor, rng):
    """The lex kind over several lane tiles and row groups, the last lane
    tile padded (300 keys in tiles of 128): counts summed over the tiles
    and the states are the oracle's."""
    from repro.sync import topology

    topo = topology.partial_mesh(6, 4)
    p = topo.max_degree
    k, per_origin, extracts = MEGA_FLAVORS[flavor]
    k = p + 1 if k == "P+1" else k
    _mega_case(rng, 4, 6, 300, p, k, "lex", per_origin, extracts, topo,
               block=(2, 128))


@pytest.mark.parametrize("flavor", sorted(MEGA_FLAVORS))
@pytest.mark.parametrize("b", [1, 3])
def test_sync_round_lex_wide_records(flavor, b, rng):
    """Records of three value planes: one lex mask selects every plane of
    the larger point, ties of the timestamp go to the value planes in
    order, and a point counts where any plane is nonzero."""
    from repro.sync import topology

    topo = topology.partial_mesh(7, 4)
    p = topo.max_degree
    k, per_origin, extracts = MEGA_FLAVORS[flavor]
    k = p + 1 if k == "P+1" else k
    _mega_case(rng, b, 7, 200, p, k, "lex", per_origin, extracts, topo,
               width=3)


@pytest.mark.parametrize("shape", [(1, 5, 100), (1, 7, 333), (64, 8, 257)])
def test_sync_round_lex_join_delta(shape, rng):
    """The lex kind's join and Δ: the oracle's lex join and Δ are the
    LWW map lattice's own, for pairs and for records of three value
    planes, and the kernel's rr round (Δ-extractions joined into the
    buffer, each slot joined into the state) is the oracle's."""
    from repro.core import LWWMap
    from repro.sync import topology

    b, n, u = shape
    for width in (1, 3):
        lat = LWWMap(num_keys=u, width=width).lattice
        pt = lambda: tuple(jnp.asarray(rng.integers(0, 5, size=shape),
                                       jnp.int32) for _ in range(1 + width))
        a, c = pt(), pt()
        for got, want in ((ref.join(a, c, "lex"), lat.join(a, c)),
                          (ref.lex_delta(c, a)[0], lat.delta(c, a))):
            assert len(got) == len(want) == 1 + width
            for g, w in zip(got, want):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    topo = topology.ring(n)
    _mega_case(rng, b, n, u, topo.max_degree, 1, "lex", False, True, topo)


@pytest.mark.parametrize("layout_block", [(1, 128), (2, 128), (4, 256)])
def test_sync_round_block_override_bit_identical(layout_block, rng):
    """Any (g, bn) tile override produces the same results — tile geometry
    is a pure performance knob (the autotuner may pick any candidate)."""
    from repro.sync import topology

    topo = topology.tree(7)
    p = topo.max_degree
    b, n, u = 4, topo.num_nodes, 300
    dtype = jnp.int32
    mk = lambda *s: jnp.asarray(rng.integers(0, 50, size=s), dtype)
    delta, x, buf = mk(b, n, u), mk(b, n, u), mk(1, b, n, u)
    active = jnp.broadcast_to(topo.mask, (b, n, p)).astype(jnp.int32)
    delivered = jnp.ones((b, n), jnp.int32)
    kw = dict(nbrs=topo.nbrs, rev=topo.rev, kind="max", per_origin=False,
              extracts=True)
    base = ops.sync_round(delta, x, buf, active, delivered, **kw)
    over = ops.sync_round(delta, x, buf, active, delivered,
                          block=layout_block, **kw)
    for g, w in zip(base, over):
        if w is not None:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["max", "bitor"])
def test_round_recv_emit_cov_vs_ref(kind, rng):
    """The optional per-element delivery tally (provenance, DESIGN.md
    §19): cov counts how many active slots delivered each universe slot
    (per-word bit tally for bitor), exactly like the oracle's."""
    p, b, u = 3, 9, 150
    hi, dtype = (50, jnp.int32) if kind == "max" else (2**31, jnp.uint32)
    d = jnp.asarray(rng.integers(0, hi, size=(p, b, u)), dtype)
    x = jnp.asarray(rng.integers(0, hi, size=(b, u)), dtype)
    active = jnp.asarray(rng.integers(0, 2, size=(b, p)), jnp.int32)
    dm = jnp.where(jnp.moveaxis(active, -1, 0)[..., None] != 0, d, 0)
    xo, s, cov, cnt, dsz = ops.round_recv(d, x, kind=kind, active=active,
                                          emit_cov=True)
    rx, rs, rcnt, rdsz, rcov = ref.round_recv(dm, x, kind=kind,
                                              emit_cov=True)
    for got, want in ((xo, rx), (s, rs), (cov, rcov), (cnt, rcnt),
                      (dsz, rdsz)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert cov.dtype == jnp.int32
    # the default path is unchanged: no tally output unless asked
    assert ops.round_recv(d, x, kind=kind, active=active)[2] is None


@pytest.mark.parametrize("layout", ["grid", "rows"])
def test_round_recv_emit_cov_batched(layout, rng):
    """Both rank-3 dispatches (sweep grid axis, store row-flattening)
    yield per-cell tallies bit-identical to unbatched calls."""
    c, p, b, u = 2, 3, 9, 150
    d = jnp.asarray(rng.integers(0, 50, size=(p, c, b, u)), jnp.int32)
    x = jnp.asarray(rng.integers(0, 50, size=(c, b, u)), jnp.int32)
    active = jnp.asarray(rng.integers(0, 2, size=(c, b, p)), jnp.int32)
    xo, _, cov, cnt, dsz = ops.round_recv(d, x, kind="max", active=active,
                                          emit_cov=True, layout=layout)
    assert cov.shape == (c, b, u)
    for cc in range(c):
        sx, _, scov, scnt, sdsz = ops.round_recv(
            d[:, cc], x[cc], kind="max", active=active[cc], emit_cov=True)
        np.testing.assert_array_equal(np.asarray(cov[cc]), np.asarray(scov))
        np.testing.assert_array_equal(np.asarray(xo[cc]), np.asarray(sx))
        np.testing.assert_array_equal(np.asarray(cnt[cc]), np.asarray(scnt))
        np.testing.assert_array_equal(np.asarray(dsz[cc]), np.asarray(sdsz))
