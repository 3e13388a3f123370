"""YCSB core workload A over a replicated cache of LWW maps, as a deployment
of the benchmark (see ``bench/spec.py`` for what a deployment module
provides).

The store is the layout of Akka Distributed Data's ``ReplicatedCache``
sample: ``objects`` top-level ``LWWMap`` entries, each replicated at every
node of a circulant partial mesh. A YCSB record of ``fields`` fields goes
to entry ``r mod objects`` (the sample's ``abs(key.hashCode % 100)``), its
field f to key ``(r div objects)·fields + f`` there, so an entry holds
``keys = records / objects · fields`` field keys. A key holds its field's
``fieldlength`` bytes on the device as ``fieldlength / 4`` int32 value
planes beside an int32 timestamp plane: a (t, v₁, …, v₂₅) record under the
lexicographic order, the newer timestamp winning with all its planes.

The start state is YCSB's load phase: every field at every replica, at
timestamp 1, its bytes drawn from the seed. Each active round every node
runs ``ops_per_node`` operations of workload A: a read (which writes
nothing) or an update of one field, chosen uniformly, of a record drawn by
YCSB's ``ScrambledZipfianGenerator`` (Zipf ranks over 10^10 items, hashed
by FNV-1a 64 onto the records). The store's op stream is the program's
``workloads.lww_write_op`` over the schedule; the write in op slot w of
node n in round t carries the timestamp ``2 + (t·N + n)·W + w``.

The plain reference below is jnp over every plane, written from the
paper's Algorithms 1 and 2 over the lex order; it imports nothing of the
program, replays the schedule's writes by its own code, and runs in blocks
of objects, one jitted program per block shape, on whatever device JAX
gives it.

A configuration names ``objects``, ``records``, ``fields``,
``fieldlength`` (bytes, a multiple of 4), ``keys``, ``nodes``, ``topology``
(``partial_mesh``), ``degree``, ``weights_bytes``, ``algorithm`` (``bprr``
or ``classic``) and ``engine``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as retwis_reference
from bench import roofline

CONTROLS = ("lossy", "unsent")
ALGORITHMS = ("bprr", "classic")

# YCSB's ScrambledZipfianGenerator at the constant 0.99: a ZipfianGenerator
# over ITEM_COUNT items with the precomputed zeta, hashed onto the records.
ITEM_COUNT = 10_000_000_000
ZETAN = 26.46902820178302
FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


# -- schedule -----------------------------------------------------------------

def fnvhash64(v: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over non-negative int64s: FNV-1a over the
    8 bytes, low byte first, then the absolute value as a signed long."""
    v = np.asarray(v, np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (v & np.uint64(0xFF))) * np.uint64(FNV_PRIME_64)
            v = v >> np.uint64(8)
    return np.abs(h.view(np.int64)).view(np.uint64)


def scrambled_zipfian(rng, records: int, theta: float, size) -> np.ndarray:
    """Record ids [size] int64 as YCSB's ``ScrambledZipfianGenerator(0,
    records - 1)`` draws them at ``theta`` 0.99."""
    if theta != 0.99:
        raise ValueError("YCSB scrambles Zipf ranks at the constant 0.99 "
                         f"alone, not {theta}")
    items = ITEM_COUNT + 1                    # ZipfianGenerator(0, ITEM_COUNT)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / ZETAN)
    u = rng.random(size)
    uz = u * ZETAN
    rank = (items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    rank = np.where(uz < 1.0 + 0.5 ** theta, 1, rank)
    rank = np.where(uz < 1.0, 0, rank)
    return (fnvhash64(rank) % np.uint64(records)).astype(np.int64)


def value_planes(config: dict) -> int:
    """int32 planes of a field's bytes."""
    if config["fieldlength"] % 4:
        raise ValueError("fieldlength must be a whole number of int32 words")
    return config["fieldlength"] // 4


def schedule(config: dict, traffic: dict, seed: int) -> dict:
    """The load records and the op tables, from one ``default_rng(seed)``
    drawn in this order: ``load`` [objects, keys, L] (the load phase's
    field bytes as L int32 words), then per op slot [T_active, N, W] the
    record, whether it is an update, the field and the value [.., L].
    Returns ``load`` and the op tables ``update``, ``object``, ``key``
    (int32), ``value`` (int32 [T, N, W, L]), and ``record``."""
    c, tr = config, traffic
    if c["records"] % c["objects"] or \
            c["keys"] != c["records"] // c["objects"] * c["fields"]:
        raise ValueError("keys must be records / objects x fields")
    words = value_planes(c)
    rng = np.random.default_rng(seed % (1 << 64))
    int32 = np.iinfo(np.int32)
    load = rng.integers(int32.min, int32.max, (c["objects"], c["keys"],
                                               words), dtype=np.int32,
                        endpoint=True)
    shape = (int(tr["active_rounds"]), c["nodes"], int(tr["ops_per_node"]))
    record = scrambled_zipfian(rng, c["records"],
                               float(tr["zipfian_constant"]), shape)
    update = rng.random(shape) < float(tr["updateproportion"])
    field = rng.integers(0, c["fields"], shape)
    value = rng.integers(int32.min, int32.max, shape + (words,),
                         dtype=np.int32, endpoint=True)
    return {"load": load,
            "update": update.astype(np.int32),
            "object": (record % c["objects"]).astype(np.int32),
            "key": (record // c["objects"] * c["fields"] + field)
            .astype(np.int32),
            "value": value,
            "record": record}


# -- the store ----------------------------------------------------------------

def weights(config: dict) -> np.ndarray:
    return np.full(config["objects"], float(config["weights_bytes"]))


def store(config: dict, sched: dict) -> tuple:
    """``(lattice, topology, StoreSpec)``: LWW maps of ``keys`` keys of
    ``fieldlength``-byte records, the loaded start state built on the
    device as ``StoreSpec.x0``, and the program's LWW write stream over the
    schedule. Raises at once where the program would not run the
    configuration's engine for these maps."""
    from repro.core import LWWMap
    from repro.sync import StoreSpec, engine, topology, workloads

    c = config
    if c["topology"] != "partial_mesh":
        raise ValueError(f"unknown topology {c['topology']!r}")
    words = value_planes(c)
    lattice = LWWMap(c["keys"], words).lattice
    ran = engine.resolve(c["engine"], lattice, resync=False)
    if ran != c["engine"]:
        raise RuntimeError(
            f"this program runs LWW maps on the {ran!r} engine, not "
            f"{c['engine']!r}: the cell measures the {c['engine']!r} kernel")
    shape = (c["objects"], c["nodes"], c["keys"])
    load = jnp.asarray(sched["load"])
    x0 = (jnp.ones(shape, jnp.int32),) + tuple(
        jnp.broadcast_to(load[:, None, :, j], shape) for j in range(words))
    tables = tuple(sched[f] for f in ("update", "object", "key", "value"))
    spec = StoreSpec(objects=c["objects"],
                     op_fn=workloads.lww_write_op(tables, c["keys"]),
                     weights=weights(c), x0=x0)
    return lattice, topology.partial_mesh(c["nodes"], c["degree"]), spec


# -- plain reference ----------------------------------------------------------

def leq(a, b):
    """The lex order of a key's (timestamp, value words) record: the first
    plane that differs decides; equal records are ⊑."""
    undecided = np.bool_(True)
    below = np.bool_(False)
    for pa, pb in zip(a, b):
        below = below | (undecided & (pa < pb))
        undecided = undecided & (pa == pb)
    return below | undecided


def _lt(a, b):
    """a < b in the lex order, in jnp: the first plane that differs."""
    undecided = jnp.ones(a[0].shape, bool)
    less = jnp.zeros(a[0].shape, bool)
    for pa, pb in zip(a, b):
        less = less | (undecided & (pa < pb))
        undecided = undecided & (pa == pb)
    return less


def _join(a, b):
    take_b = _lt(a, b)
    return tuple(jnp.where(take_b, pb, pa) for pa, pb in zip(a, b))


def _nonbottom(a):
    out = a[0] != 0
    for leaf in a[1:]:
        out = out | (leaf != 0)
    return out


def _size(a):
    return jnp.sum(_nonbottom(a), axis=-1, dtype=jnp.int32)


def _where(mask, a):
    return tuple(jnp.where(mask, leaf, 0) for leaf in a)


def newest_writes(sched: dict, nodes: int, objects: int) -> tuple:
    """Per active round, the newest write of each (object, node, key) the
    round writes: ``(object, node, key, timestamp, value)``, the first
    four [T, M] int32 and ``value`` [T, M, L] int32, with M = N·W, padded
    with object id ``objects``."""
    upd = sched["update"]
    rounds, n, w = upd.shape
    words = sched["value"].shape[-1]
    m = n * w
    node = np.broadcast_to(np.arange(n)[:, None], (n, w)).ravel()
    slot = np.broadcast_to(np.arange(w)[None, :], (n, w)).ravel()
    out = np.zeros((4, rounds, m), np.int64)
    out[0] = objects
    value = np.zeros((rounds, m, words), np.int32)
    for t in range(rounds):
        on = upd[t].ravel() != 0
        o = sched["object"][t].ravel()[on].astype(np.int64)
        k = sched["key"][t].ravel()[on]
        nn, ww = node[on], slot[on]
        stamp = 2 + (t * n + nn) * w + ww
        order = np.argsort(-stamp, kind="stable")         # newest first
        cell = ((o * n + nn) * (1 << 20) + k)[order]
        _, first = np.unique(cell, return_index=True)
        pick = order[first]
        for i, col in enumerate((o[pick], nn[pick], k[pick], stamp[pick])):
            out[i, t, :len(pick)] = col
        value[t, :len(pick)] = sched["value"][t].reshape(m, words)[on][pick]
    return tuple(a.astype(np.int32) for a in out) + (value,)


@functools.partial(jax.jit, static_argnames=(
    "algorithm", "rounds", "active", "control", "nbrs", "rev"))
def _block(lo, load, writes, *, algorithm, rounds, active, control, nbrs,
           rev):
    """One block of objects [lo, lo + b): ``load`` [b, keys, L], ``writes``
    the five tables of ``newest_writes``."""
    b, keys, words = load.shape
    n, p = len(nbrs), len(nbrs[0])
    k = p + 1 if algorithm == "bprr" else 1
    x0 = (jnp.ones((b, n, keys), jnp.int32),) + tuple(
        jnp.broadcast_to(load[:, None, :, j], (b, n, keys))
        for j in range(words))
    zero = jnp.zeros((b, n, keys), jnp.int32)
    bot = (zero,) * (1 + words)
    buf0 = [bot] * k
    acked0 = (jnp.ones((b, keys), jnp.int32),) + tuple(
        load[:, :, j] for j in range(words))
    wo, wn, wk, ws, wv = writes

    def round_(carry, t):
        x, buf, acked = carry
        buf = list(buf)
        # (1) local update: the newest write of each key a node writes
        tt = jnp.minimum(t, active - 1)
        mine = (t < active) & (wo[tt] >= lo) & (wo[tt] < lo + b)
        o = jnp.where(mine, wo[tt] - lo, b)                # b: dropped
        delta = (zero.at[o, wn[tt], wk[tt]].set(ws[tt], mode="drop"),) + \
            tuple(zero.at[o, wn[tt], wk[tt]].set(wv[tt][:, j], mode="drop")
                  for j in range(words))
        cpu = jnp.sum(_size(delta), axis=1)
        x = _join(x, delta)
        # the newest of the nodes' writes of a key, with its whole record
        first = jnp.argmax(delta[0], axis=1)[:, None]      # [b, 1, keys]
        acked = _join(acked, tuple(
            jnp.take_along_axis(leaf, first, axis=1)[:, 0] for leaf in delta))
        if control == "unsent":
            delta = _where((jnp.arange(n) != 0)[None, :, None], delta)
        buf[k - 1] = _join(buf[k - 1], delta)
        # (2) sends: send[q][:, i] goes from node i to its neighbour q
        if algorithm == "bprr":
            send = []
            for q in range(p):
                acc = bot
                for r in range(k):
                    if r != q:
                        acc = _join(acc, buf[r])
                send.append(acc)
        else:
            send = [buf[0]] * p
        tx = sum(jnp.sum(_size(s), axis=1) for s in send)
        cpu = cpu + tx
        # (3) clear every buffer
        buf = [bot] * k
        held = jnp.zeros((b, n), jnp.int32)
        # (4) receive, slot by slot
        sent = tuple(jnp.stack([s[j] for s in send], axis=2)
                     for j in range(1 + words))            # [b, N, P, keys]
        for q in range(p):
            src = np.asarray([nbrs[i][q] for i in range(n)])
            slot = np.asarray([rev[i][q] for i in range(n)])
            d = tuple(a[:, src, slot] for a in sent)       # [b, N, keys]
            if control == "lossy" and q == 0:
                d = bot
            novel = _lt(x, d)                              # d ⋢ x
            if algorithm == "bprr":
                stored = _where(novel & _nonbottom(d), d)
                keep = jnp.any(_nonbottom(stored), axis=-1)
                buf[q] = _join(buf[q], stored)
            else:
                stored = d
                keep = jnp.any(novel, axis=-1)
                buf[0] = _join(buf[0], _where(keep[..., None], d))
            ssz = _size(stored) * keep
            cpu = cpu + jnp.sum(_size(d), axis=1) + jnp.sum(ssz, axis=1)
            held = held + ssz
            x = _join(x, d)
        node_mem = _size(x) + held
        uniform = jnp.all(jnp.stack([jnp.all(leaf == leaf[:, :1], axis=(1, 2))
                                     for leaf in x]), axis=0)
        out = (tx, jnp.sum(node_mem, axis=1), cpu, jnp.max(node_mem, axis=1),
               uniform)
        return (x, tuple(buf), acked), out

    (x, _, acked), ys = jax.lax.scan(round_, (x0, tuple(buf0), acked0),
                                     jnp.arange(rounds))
    return (x, acked) + tuple(a.T for a in ys)


def reference(config: dict, sched: dict, rounds: int, control=None,
              block: int = 10) -> dict:
    """The plain reference's outputs (with ``control``: that control's):
    ``final_x`` ([B, N, keys] timestamps, then value words), ``acked``
    ([B, keys] record), and [B, T] ``tx``, ``mem``, ``cpu``,
    ``max_mem_node`` (int64), ``uniform`` and ``tx_bytes``.

    ``lossy``: each node never receives from its first neighbour slot while
    the sender still clears its buffer (delivery). ``unsent``: node 0
    applies its writes but never buffers them (every acknowledged write
    reaches every replica; convergence)."""
    c = config
    if control is not None and control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; one of {CONTROLS}")
    if c["algorithm"] not in ALGORITHMS:
        raise ValueError(f"no reference for {c['algorithm']!r}")
    objects = c["objects"]
    nbrs, rev = retwis_reference.partial_mesh(c["nodes"], c["degree"])
    writes = tuple(jnp.asarray(a) for a in
                   newest_writes(sched, c["nodes"], objects))
    load = np.asarray(sched["load"], np.int32)
    planes = 1 + load.shape[-1]
    block = min(block, objects)
    parts = []
    for lo in range(0, objects, block):
        chunk = load[lo:lo + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.pad(chunk, ((0, pad), (0, 0), (0, 0)))
        outs = _block(jnp.int32(lo), jnp.asarray(chunk), writes,
                      algorithm=c["algorithm"], rounds=rounds,
                      active=int(sched["update"].shape[0]), control=control,
                      nbrs=tuple(map(tuple, nbrs.tolist())),
                      rev=tuple(map(tuple, rev.tolist())))
        outs = jax.tree.map(lambda a: np.asarray(a)[:block - pad], outs)
        parts.append(outs)
    x = tuple(np.concatenate([o[0][j] for o in parts]) for j in range(planes))
    acked = tuple(np.concatenate([o[1][j] for o in parts])
                  for j in range(planes))
    cols = [np.concatenate([o[i] for o in parts]) for i in range(2, 7)]
    res = dict(zip(("tx", "mem", "cpu", "max_mem_node", "uniform"), cols))
    for f in ("tx", "mem", "cpu", "max_mem_node"):
        res[f] = res[f].astype(np.int64)
    res["final_x"], res["acked"] = x, acked
    res["tx_bytes"] = res["tx"].astype(np.float64) * weights(c)[:, None]
    return res


def round_bytes(config: dict) -> int:
    """Bytes one sync round must read and write: the Retwis count (delta,
    state and buffers read; state and buffers written) for each of a
    key's int32 planes, the timestamp and the field's words."""
    c = config
    return (1 + value_planes(c)) * roofline.round_bytes(
        c["algorithm"], c["objects"], c["nodes"], c["degree"], c["keys"])
