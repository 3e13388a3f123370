"""Compile every engine kernel for a TPU v5e that is described, not attached.

Interpret mode (how every other test runs the kernels) accepts blocks and
vector shapes that the chip's Mosaic compiler refuses. These tests lower
the kernel wrappers with ``interpret=False`` for one chip of a described
``v5e:2x2`` topology and compile them with the installed TPU compiler,
under ``jax.enable_x64(True)`` as the simulator's scan traces them. Shapes
are those of ``chip_smoke.py``'s Retwis store (30,000 objects of 64
versioned slots on a 50-node, degree-4 mesh, ``rows`` layout) and the
paper's Table I cells (15-node mesh; a boolean GSet and a bit-packed
BitGSet of 1,800 words). Nothing runs; a compile error fails the test.

The topology is described inside a module fixture, so only the worker
that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops
from repro.sync import topology

STORE_B, STORE_N, STORE_U = 30_000, 50, 64
T1_N, T1_U = 15, 1_800
P = 4


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    old_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", old_cache)
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)


def compile_for_chip(fn, *shapes):
    """Lower and compile ``fn`` at ``shapes`` for the described chip; the
    compiled program must hold a Mosaic kernel."""
    with jax.enable_x64(True):
        compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _round(one_chip, algo, b, n, u, dtype, kind):
    """Compile ``kops.sync_round`` as ``engine.mega_round`` calls it for
    ``algo`` over a partial mesh of n nodes."""
    topo = topology.partial_mesh(n, P)
    k = {"bprr": P + 1, "classic": 1, "state": 0}[algo]
    per_origin, extracts = algo == "bprr", algo == "bprr"

    def step(d, x, buf, act, dlv):
        return kops.sync_round(d, x, buf, act, dlv, nbrs=topo.nbrs,
                               rev=topo.rev, kind=kind,
                               per_origin=per_origin, extracts=extracts,
                               layout="rows", interpret=False)

    d = _spec(one_chip, (b, n, u), dtype)
    act = _spec(one_chip, (b, n, P), jnp.bool_)
    if not k:
        return compile_for_chip(
            lambda d, x, act: step(d, x, None, act, None), d, d, act)
    return compile_for_chip(step, d, d, _spec(one_chip, (k, b, n, u), dtype),
                            act, _spec(one_chip, (b, n), jnp.bool_))


@pytest.mark.parametrize("algo", ["bprr", "classic", "state"])
def test_round_step_compiles_at_store_width(one_chip, algo):
    _round(one_chip, algo, STORE_B, STORE_N, STORE_U, jnp.int32, "max")


@pytest.mark.parametrize("algo", ["bprr", "classic"])
def test_round_step_compiles_for_wide_lex_records(one_chip, algo):
    """The lex kind at the YCSB cell's tile: 26 planes a point (a
    timestamp and a 100 B field's 25 words) of 1,280 keys on a 50-node
    mesh, a few objects (the tile, and so the VMEM it takes, does not
    depend on their count)."""
    topo = topology.partial_mesh(STORE_N, P)
    b, u, planes = 4, 1_280, 26
    k = P + 1 if algo == "bprr" else 1
    blk, _ = kops.sync_round_block(b, STORE_N, u, p=P, k=k, kind="lex",
                                   layout="rows", n_planes=planes)
    assert blk == (1, 128)

    def step(d, x, buf, act, dlv):
        return kops.sync_round(d, x, buf, act, dlv, nbrs=topo.nbrs,
                               rev=topo.rev, kind="lex",
                               per_origin=algo == "bprr",
                               extracts=algo == "bprr", layout="rows",
                               interpret=False)

    point = lambda *s: (_spec(one_chip, s, jnp.int32),) * planes
    compile_for_chip(step, point(b, STORE_N, u), point(b, STORE_N, u),
                     point(k, b, STORE_N, u),
                     _spec(one_chip, (b, STORE_N, P), jnp.bool_),
                     _spec(one_chip, (b, STORE_N), jnp.bool_))


@pytest.mark.parametrize("dtype,kind", [(jnp.bool_, "max"),
                                        (jnp.uint32, "bitor")],
                         ids=["gset_bool", "bitgset_uint32"])
def test_round_step_compiles_at_table1_width(one_chip, dtype, kind):
    _round(one_chip, "bprr", 1, T1_N, T1_U, dtype, kind)


@pytest.mark.parametrize("dtype,kind", [(jnp.int32, "max"),
                                        (jnp.bool_, "max"),
                                        (jnp.uint32, "bitor")])
def test_round_recv_compiles(one_chip, dtype, kind):
    compile_for_chip(
        lambda d, x, a: kops.round_recv(d, x, kind=kind, active=a,
                                        emit_cov=kind == "bitor",
                                        layout="rows", interpret=False),
        _spec(one_chip, (P, 4, T1_N, T1_U), dtype),
        _spec(one_chip, (4, T1_N, T1_U), dtype),
        _spec(one_chip, (4, T1_N, P), jnp.bool_))


@pytest.mark.parametrize("layout", ["rows", "grid"])
def test_buffer_fold_compiles(one_chip, layout):
    compile_for_chip(
        lambda b: kops.buffer_fold(b, kind="max", batched=True,
                                   layout=layout, interpret=False),
        _spec(one_chip, (P + 1, 64, STORE_N, STORE_U), jnp.int32))


@pytest.mark.parametrize("be", [8, 32, 128])
@pytest.mark.parametrize("kind", ["max", "bitor"])
def test_digest_blocks_compiles(one_chip, kind, be):
    compile_for_chip(
        lambda x: kops.digest_blocks(x, block_elems=be, kind=kind,
                                     batched=True, interpret=False),
        _spec(one_chip, (4, T1_N, 4_000), jnp.int32))


@pytest.mark.parametrize("be", [8, 32, 128])
def test_masked_extract_compiles(one_chip, be):
    nb = -(-4_000 // be)
    compile_for_chip(
        lambda x, m: kops.masked_extract(x, m, block_elems=be, batched=True,
                                         interpret=False),
        _spec(one_chip, (4, T1_N, 4_000), jnp.bool_),
        _spec(one_chip, (4, T1_N, P, nb), jnp.bool_))
