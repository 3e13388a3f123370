"""A toy second deployment for the harness's tests: a store of LWW maps.

Each object is a map of ``keys`` keys to lexicographic (timestamp, value)
pairs (the program's ``LWWMap``), so a state is a pytree of two [B, N,
keys] int32 leaves, replicated at every node of a partial mesh. In an
active round a node writes with probability ``write_prob`` one key of an
object, drawn from the seed with its value: the write's timestamp is one
above the largest the node's replica holds (a Lamport clock), so it
overwrites the key there. The plain reference below is numpy, written
from the paper's Algorithms 1 and 2 over the lex order, and imports
nothing of the program.
"""

from __future__ import annotations

import dataclasses

import numpy as np

CONTROLS = ("unsent",)


def schedule(config: dict, traffic: dict, seed: int) -> tuple:
    """``(write, key, value)``, each [T_active, N, B] int32."""
    rng = np.random.default_rng(seed % (1 << 64))
    shape = (int(traffic["active_rounds"]), config["nodes"],
             config["objects"])
    write = rng.random(shape) < traffic["write_prob"]
    key = rng.integers(0, config["keys"], shape)
    value = rng.integers(1, 1000, shape)
    return tuple(a.astype(np.int32) for a in (write, key, value))


@dataclasses.dataclass(frozen=True)
class _Put:
    """The op stream's program over the [T, B, N] schedule tables."""

    keys: int

    def __call__(self, operands, x, t):
        import jax.numpy as jnp

        ts, _ = x                                       # [B, N, keys]
        write, key, value = (o[t] for o in operands)    # [B, N]
        clock = jnp.max(ts, axis=-1) + 1
        hit = (write[..., None] != 0) & (
            jnp.arange(self.keys) == key[..., None])
        return (jnp.where(hit, clock[..., None], 0),
                jnp.where(hit, value[..., None], 0))


def store(config: dict, sched) -> tuple:
    import jax.numpy as jnp
    from repro.core import LWWMap
    from repro.sync import StoreSpec, topology, workloads

    c = config
    tables = tuple(jnp.asarray(np.transpose(a, (0, 2, 1))) for a in sched)
    spec = StoreSpec(objects=c["objects"],
                     op_fn=workloads.OpStream(_Put(c["keys"]), tables),
                     weights=np.full(c["objects"], float(c["weight_bytes"])))
    return (LWWMap(c["keys"]).lattice,
            topology.partial_mesh(c["nodes"], c["degree"]), spec)


# -- plain reference ----------------------------------------------------------

def leq(a, b):
    (ta, va), (tb, vb) = a, b
    return (ta < tb) | ((ta == tb) & (va <= vb))


def _join(a, b):
    (ta, va), (tb, vb) = a, b
    return (np.maximum(ta, tb),
            np.where(ta == tb, np.maximum(va, vb), np.where(ta > tb, va, vb)))


def _where(mask, a):
    return tuple(np.where(mask, leaf, 0) for leaf in a)


def _nonbottom(a):
    return (a[0] != 0) | (a[1] != 0)


def _size(a):
    return np.sum(_nonbottom(a), axis=-1)


def _mesh(nodes: int, degree: int):
    lists = [sorted({(i + s * o) % nodes for o in range(1, degree // 2 + 1)
                     for s in (1, -1)}) for i in range(nodes)]
    rev = [[lists[j].index(i) for j in lists[i]] for i in range(nodes)]
    return np.asarray(lists), np.asarray(rev)


def reference(config: dict, sched, rounds: int, control=None) -> dict:
    c = config
    write, key, value = sched
    active, n, b = write.shape
    p = c["degree"]
    k = p + 1 if c["algorithm"] == "bprr" else 1
    nbrs, rev = _mesh(n, p)
    zero = np.zeros((b, n, c["keys"]), np.int32)
    x = (zero, zero)
    acked = (zero[:, 0], zero[:, 0])
    lanes = np.arange(c["keys"])
    buf = [(zero, zero) for _ in range(k)]
    cols = {f: [] for f in ("tx", "mem", "cpu", "max_mem_node", "uniform")}
    for t in range(rounds):
        cpu = np.zeros(b, np.int64)
        if t < active:                                   # local writes
            w, kk, v = (a[t].T for a in (write, key, value))   # [B, N]
            hit = (w[..., None] != 0) & (lanes == kk[..., None])
            clock = x[0].max(axis=-1) + 1
            delta = (np.where(hit, clock[..., None], 0).astype(np.int32),
                     np.where(hit, v[..., None], 0).astype(np.int32))
            cpu += _size(delta).sum(axis=1)
            x = _join(x, delta)
            for i in range(n):
                acked = _join(acked, tuple(a[:, i] for a in delta))
            if control == "unsent":
                delta = tuple(a * (np.arange(n) != 0)[None, :, None]
                              for a in delta)
            buf[k - 1] = _join(buf[k - 1], delta)
        if c["algorithm"] == "bprr":
            send = []
            for q in range(p):
                acc = (zero, zero)
                for r in range(k):
                    if r != q:
                        acc = _join(acc, buf[r])
                send.append(acc)
        else:
            send = [buf[0]] * p
        tx = sum(_size(s).sum(axis=1) for s in send)
        cpu += tx
        buf = [(zero, zero) for _ in range(k)]
        held = np.zeros((b, n), np.int64)
        for q in range(p):
            d = tuple(np.stack([send[rev[i, q]][j][:, nbrs[i, q]]
                                for i in range(n)], axis=1) for j in (0, 1))
            novel = ~leq(d, x)
            if c["algorithm"] == "bprr":
                stored = _where(novel & _nonbottom(d), d)
                keep = _size(stored) > 0
                buf[q] = _join(buf[q], stored)
            else:
                stored = d
                keep = novel.any(axis=-1)
                buf[0] = _join(buf[0], _where(keep[..., None], d))
            ssz = _size(stored) * keep
            cpu += _size(d).sum(axis=1) + ssz.sum(axis=1)
            held += ssz
            x = _join(x, d)
        node_mem = _size(x) + held
        cols["tx"].append(tx)
        cols["mem"].append(node_mem.sum(axis=1))
        cols["cpu"].append(cpu)
        cols["max_mem_node"].append(node_mem.max(axis=1))
        cols["uniform"].append(np.all([np.all(a == a[:, :1], axis=(1, 2))
                                       for a in x], axis=0))
    out = {f: np.stack(v, axis=1) for f, v in cols.items()}
    out["final_x"], out["acked"] = x, acked
    out["tx_bytes"] = out["tx"].astype(np.float64) * c["weight_bytes"]
    return out


def round_bytes(config: dict) -> int:
    """Two int32 leaves an element: the Retwis count (delta, state and
    buffers read; state and buffers written) per leaf."""
    c = config
    k = c["degree"] + 1 if c["algorithm"] == "bprr" else 1
    plane = c["objects"] * c["nodes"] * c["keys"] * 4 * 2
    return plane * (2 + k) + plane * (1 + k)
