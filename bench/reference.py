"""Plain reference of the replicated store: the same operations on the same
data, written from the paper's Algorithms 1 and 2 and nothing of the
program under test.

Each object is a map of ``slots`` versioned slots under ``max`` (a slot
holds 0 when unset), replicated at every node of a circulant partial mesh.
One round, per object and node, in this order:

1. local update (active rounds only): the node bumps ``cnt`` slots, from
   the slot its current version (largest slot value) points at, to their
   value + 1; the delta joins the state and the local buffer;
2. send: classic (Algorithm 1) sends its one buffer to every neighbour;
   BP+RR (Algorithm 2) keeps one buffer per origin (each neighbour, plus
   local updates) and sends neighbour p the join of every buffer but p's;
3. every buffer is cleared (a fault-free network delivers every message);
4. receive, neighbour slot by slot in ascending neighbour id: classic
   buffers the whole group when it inflates the state, BP+RR buffers only
   the part that is new to the state (the Δ-extraction), under the slot
   of its origin; the group then joins the state.

Per object and round it reports the elements sent (``tx``), held in state
and buffers at round end (``mem``, and its largest node ``max_mem_node``),
the element operations (``cpu``: local delta sizes, sent sizes, received
sizes and buffered sizes) and whether every replica holds the same state
(``uniform``); also the final states and ``acked``, the join of every
update a node applied, which every replica must hold at the end.

``control`` names a control of the comparison, a reference with one of the
configuration's guarantees broken, as a shortcut that drops or skips work
would break it:

* ``"lossy"``: each node never receives from its first neighbour slot,
  while the sender still clears its buffer (delivery: every δ-group sent
  is delivered);
* ``"unsent"``: node 0 applies its updates to its own state but never
  buffers them, so no other replica learns of them (every acknowledged
  update reaches every replica; convergence).

Objects are independent, so the reference runs in blocks of objects, one
jitted program per block shape, on whatever device JAX gives it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ALGORITHMS = ("bprr", "classic")
CONTROLS = ("lossy", "unsent")


def partial_mesh(nodes: int, degree: int):
    """Neighbour table [N, P] (ascending ids), and for receiver i and slot
    q the slot of i in its sender's table ([N, P]). Node i links with the
    nodes at ring offsets ±1..±degree/2."""
    if degree % 2 or not 0 < degree < nodes:
        raise ValueError(f"a partial mesh needs an even degree below the "
                         f"node count, got degree {degree} on {nodes}")
    lists = [sorted({(i + s * o) % nodes for o in range(1, degree // 2 + 1)
                     for s in (1, -1)}) for i in range(nodes)]
    if any(len(l) != degree for l in lists):
        raise ValueError(f"{nodes} nodes are too few for degree {degree}")
    nbrs = np.asarray(lists, np.int32)
    rev = np.asarray([[lists[j].index(i) for j in lists[i]]
                      for i in range(nodes)], np.int32)
    return nbrs, rev


def _size(a):
    return jnp.sum(a != 0, axis=-1, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "algorithm", "rounds", "active_rounds", "slots", "control"))
def _block(counts, nbrs, rev, *, algorithm, rounds, active_rounds, slots,
           control):
    """One block of objects. ``counts`` [T_active, N, b] int32."""
    _, n, b = counts.shape
    p = nbrs.shape[1]
    k = p + 1 if algorithm == "bprr" else 1
    x0 = jnp.zeros((b, n, slots), jnp.int32)
    buf0 = jnp.zeros((b, n, k, slots), jnp.int32)
    lanes = jnp.arange(slots, dtype=jnp.int32)

    def round_(carry, t):
        x, buf, acked = carry
        # (1) local update
        cnt = counts[jnp.minimum(t, active_rounds - 1)].T      # [b, N]
        cnt = jnp.where(t < active_rounds, cnt, 0)
        ver = jnp.max(x, axis=-1, keepdims=True)
        first = ver % slots
        picked = (lanes - first) % slots < cnt[..., None]
        delta = jnp.where(picked, x + 1, 0)
        dsz = _size(delta)
        x = jnp.maximum(x, delta)
        acked = jnp.maximum(acked, jnp.max(delta, axis=1))
        if control == "unsent":
            delta = delta.at[:, 0].set(0)
        buf = buf.at[:, :, k - 1].max(delta)
        cpu = jnp.sum(dsz, axis=1)
        # (2) sends: send[:, i, q] goes from node i to its neighbour q
        if algorithm == "bprr":
            send = jnp.stack([jnp.max(jnp.concatenate(
                [buf[:, :, :q], buf[:, :, q + 1:]], axis=2), axis=2)
                for q in range(p)], axis=2)
        else:
            send = jnp.broadcast_to(buf, (b, n, p, slots))
        tx = jnp.sum(_size(send), axis=(1, 2))
        cpu = cpu + tx
        # (3) clear every buffer
        buf = jnp.zeros_like(buf)
        held = jnp.zeros((b, n), jnp.int32)
        # (4) receive, slot by slot
        for q in range(p):
            d = send[:, nbrs[:, q], rev[:, q]]                   # [b, N, U]
            if control == "lossy" and q == 0:
                d = jnp.zeros_like(d)
            if algorithm == "bprr":
                stored = jnp.where((d > x) & (d != 0), d, 0)
                keep = jnp.any(stored != 0, axis=-1)
                buf = buf.at[:, :, q].max(stored)
            else:
                stored = d
                keep = jnp.any(d > x, axis=-1)
                buf = buf.at[:, :, 0].max(
                    jnp.where(keep[..., None], d, 0))
            ssz = _size(stored) * keep
            cpu = cpu + jnp.sum(_size(d), axis=1) + jnp.sum(ssz, axis=1)
            held = held + ssz
            x = jnp.maximum(x, d)
        node_mem = _size(x) + held
        uniform = jnp.all(x == x[:, :1], axis=(1, 2))
        out = (tx, jnp.sum(node_mem, axis=1), cpu, jnp.max(node_mem, axis=1),
               uniform)
        return (x, buf, acked), out

    acked0 = jnp.zeros((b, slots), jnp.int32)
    (x, _, acked), ys = jax.lax.scan(round_, (x0, buf0, acked0),
                                     jnp.arange(rounds))
    tx, mem, cpu, mmax, uni = (a.T for a in ys)                 # [b, T]
    return x, acked, tx, mem, cpu, mmax, uni


def simulate(counts: np.ndarray, *, nodes: int, degree: int, slots: int,
             algorithm: str, rounds: int, block: int = 6000,
             control: str | None = None) -> dict:
    """The store over every object of ``counts`` ([T_active, N, B] update
    counts), ``rounds`` rounds in all. Returns host arrays: ``final_x``
    [B, N, slots], ``acked`` [B, slots], and [B, T] ``tx``, ``mem``,
    ``cpu``, ``max_mem_node`` (int64) and ``uniform`` (bool)."""
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    if algorithm not in ALGORITHMS:
        raise ValueError(f"no reference for {algorithm!r}; one of "
                         f"{ALGORITHMS}")
    counts = np.asarray(counts, np.int32)
    active, n, objects = counts.shape
    if n != nodes or not 1 <= active <= rounds:
        raise ValueError(f"counts {counts.shape} do not fit {nodes} nodes "
                         f"and {rounds} rounds")
    nbrs, rev = partial_mesh(nodes, degree)
    block = min(block, objects)
    parts = []
    for lo in range(0, objects, block):
        chunk = counts[:, :, lo:lo + block]
        pad = block - chunk.shape[2]
        if pad:
            chunk = np.pad(chunk, ((0, 0), (0, 0), (0, pad)))
        outs = _block(jnp.asarray(chunk), jnp.asarray(nbrs), jnp.asarray(rev),
                      algorithm=algorithm, rounds=rounds,
                      active_rounds=active, slots=slots, control=control)
        parts.append([np.asarray(a)[:block - pad] for a in outs])
    cat = [np.concatenate(cols, axis=0) for cols in zip(*parts)]
    names = ("final_x", "acked", "tx", "mem", "cpu", "max_mem_node",
             "uniform")
    res = dict(zip(names, cat))
    for f in ("tx", "mem", "cpu", "max_mem_node"):
        res[f] = res[f].astype(np.int64)
    return res
