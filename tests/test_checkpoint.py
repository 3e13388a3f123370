"""Checkpoint layer (DESIGN.md §16): bundle round-trips, restore-time
verification (digest / tree paths / shapes — a corrupted or mismatched
bundle must raise, never silently restore garbage), and the CRDT
checkpoint registry converging over gossip."""

import json

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer, CheckpointRegistry
from repro.runtime.gossip import GossipNode, LocalTransport, converged, sync_round


def _state():
    return {
        "params": {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), ml_dtypes.bfloat16) * 1.5,
        },
        "mask": jnp.asarray([True, False, True]),
        "step": np.arange(6, dtype=np.int64),
    }


def _like(state):
    return jax.tree.map(lambda a: np.zeros(np.shape(a), np.asarray(a).dtype),
                        state)


# -- round trips --------------------------------------------------------------

def test_roundtrip_mixed_dtypes(tmp_path):
    """bf16 (saved as uint16 view), bool, int64 and f32 leaves all come
    back bit-exact with their true dtypes."""
    ck = Checkpointer(tmp_path)
    state = _state()
    digest = ck.save(3, state, extra={"note": "t"})
    assert ck.available_steps() == [3]
    mf = ck.manifest(3)
    assert mf["digest"] == digest and mf["extra"] == {"note": "t"}
    with jax.enable_x64(True):          # keep int64 leaves wide
        out = ck.restore(3, _like(state))
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        assert got.dtype == np.asarray(want).dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_roundtrip_latest_of_many(tmp_path):
    ck = Checkpointer(tmp_path)
    for s in (1, 2, 5):
        ck.save(s, {"x": np.full((2,), s)})
    assert ck.available_steps() == [1, 2, 5]
    out = ck.restore(5, {"x": np.zeros((2,), np.int64)})
    np.testing.assert_array_equal(np.asarray(out["x"]), [5, 5])


# -- restore-time verification ------------------------------------------------

def test_restore_rejects_bitflip(tmp_path):
    ck = Checkpointer(tmp_path)
    state = _state()
    ck.save(1, state)
    bundle = tmp_path / "step_00000001" / "arrays.npz"
    raw = bytearray(bundle.read_bytes())
    raw[len(raw) // 2] ^= 0xFF                  # flip one payload byte
    bundle.write_bytes(bytes(raw))
    with pytest.raises(ValueError, match="digest|unreadable"):
        ck.restore(1, _like(state))


def test_restore_rejects_truncation(tmp_path):
    ck = Checkpointer(tmp_path)
    state = _state()
    ck.save(1, state)
    bundle = tmp_path / "step_00000001" / "arrays.npz"
    bundle.write_bytes(bundle.read_bytes()[: bundle.stat().st_size // 3])
    with pytest.raises(ValueError, match="unreadable|truncated|digest"):
        ck.restore(1, _like(state))


def test_restore_rejects_manifest_tamper(tmp_path):
    ck = Checkpointer(tmp_path)
    state = _state()
    ck.save(1, state)
    mpath = tmp_path / "step_00000001" / "manifest.json"
    mf = json.loads(mpath.read_text())
    mf["digest"] = "0" * 16
    mpath.write_text(json.dumps(mf))
    with pytest.raises(ValueError, match="digest"):
        ck.restore(1, _like(state))


def test_restore_rejects_renamed_leaf(tmp_path):
    """A tree whose paths moved since the save must fail loudly — the
    arrays would otherwise land on the wrong leaves."""
    ck = Checkpointer(tmp_path)
    ck.save(1, {"a": np.zeros((2,)), "b": np.ones((2,))})
    with pytest.raises(ValueError, match="reordered or renamed"):
        ck.restore(1, {"a": np.zeros((2,)), "c": np.ones((2,))})


def test_restore_rejects_leaf_count_mismatch(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"a": np.zeros((2,)), "b": np.ones((2,))})
    with pytest.raises(ValueError, match="leaves"):
        ck.restore(1, {"a": np.zeros((2,))})


def test_restore_rejects_shape_mismatch(tmp_path):
    ck = Checkpointer(tmp_path)
    ck.save(1, {"a": np.zeros((2, 3))})
    with pytest.raises(ValueError, match="shape"):
        ck.restore(1, {"a": np.zeros((3, 2))})


# -- checkpoint registry over gossip ------------------------------------------

def test_registry_announce_merge_latest():
    a, b = CheckpointRegistry(capacity=16), CheckpointRegistry(capacity=16)
    assert a.latest_step() is None
    d1 = a.announce(3)
    d2 = a.announce(7)
    b.merge(d2)
    b.merge(d1)                                  # order-free (join)
    b.merge(d1)                                  # duplicate-free (idempotent)
    assert a.latest_step() == b.latest_step() == 7


def test_registry_gossip_convergence():
    """Every node learns the newest durable step via BP+RR gossip — no
    metadata service, just the registry GMap's optimal deltas."""
    regs = {i: CheckpointRegistry(capacity=32) for i in range(4)}
    lat = regs[0].gmap.lattice
    transport = LocalTransport()
    ring = {0: [1, 3], 1: [0, 2], 2: [1, 3], 3: [2, 0]}
    nodes = {}
    for i, nbrs in ring.items():
        nodes[i] = GossipNode(i, nbrs, transport)
        nodes[i].register("ckpt", lat, state=regs[i].state)
    # different nodes durably wrote different steps
    nodes[0].update("ckpt", regs[0].announce(11))
    nodes[2].update("ckpt", regs[2].announce(29))
    for _ in range(4):
        sync_round(nodes)
        if converged(nodes, "ckpt"):
            break
    assert converged(nodes, "ckpt")
    for i in ring:
        regs[i].state = nodes[i].state("ckpt")
        assert regs[i].latest_step() == 29
