"""Reproducible workload generators for stores and micro-benchmarks
(DESIGN.md §15).

Two families of op-stream builders used to live scattered across
``benchmarks/``:

* **Keyed store workloads** — the paper's Retwis macro-benchmark (§V-D,
  Table II) targets *objects* of a store via a Zipf distribution and
  draws op kinds (follow / post / read) from a fixed mix.
  ``WorkloadSpec`` captures that shape declaratively: an object-targeting
  distribution (``zipf`` / ``uniform`` / ``hotset``), an op-kind mix with
  per-kind update counts, and a seed. It compiles to dense per-round
  update-count tables ``[T, N, B]`` and to the batched op streams the
  store engine (``sync/store.py``) and ``simulate_sweep`` consume.
  Streams are seed-deterministic: the same spec and seed always produce
  the same schedule (one ``np.random.default_rng(seed)`` drawn in a fixed
  call order), which is what lets ``benchmarks/fig11_retwis.py`` on the
  store API reproduce its pre-store numbers exactly.

* **Table I micro-benchmark streams** — the unique-element GSet adds,
  per-replica GCounter increments, and disjoint GMap key blocks that the
  Fig 7–10 harnesses share (``benchmarks/common.py`` re-exports these).
  The seed-permutation scheme of the sweep variants (seed 0 = identity =
  the paper-canonical stream) lives here too.

Everything host-side is plain numpy, built once. An op stream ships its
tables to the device either as the operands of an :class:`OpStream`
(``versioned_slot_op``: arguments of the compiled program, which the store
then compiles once per shape) or as constants an op_fn closes over.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Retwis byte sizes (paper §V-D): tweet ids, tweet content, node/user ids.
ID_B, CONTENT_B, USER_B = 31, 270, 20
FOLLOW_B = USER_B                 # follower entry: one user id
WALL_B = ID_B + CONTENT_B         # wall entry: tweet id + content
TL_B = ID_B + 8                   # timeline entry: tweet id + timestamp

DISTS = ("zipf", "uniform", "hotset")


@dataclasses.dataclass(frozen=True)
class OpKind:
    """One op kind of a mix: drawn with probability ``prob``; each drawn op
    updates ``updates`` elements of its target object (0 = pure read)."""

    name: str
    prob: float
    updates: int = 1


# Paper Table II: 15% follow (1 update), 35% post (1 update on the target
# wall/timeline object), 50% timeline read (no updates).
RETWIS_MIX = (OpKind("follow", 0.15, 1),
              OpKind("post", 0.35, 1),
              OpKind("read", 0.50, 0))


@dataclasses.dataclass(frozen=True)
class WorkloadSpec:
    """A keyed-store workload: B objects targeted per (round, node, op)
    by ``dist``, op kinds drawn from ``mix``.

    ``zipf`` is the contention coefficient (rank-probability ∝ rank^-zipf);
    ``hotset`` puts ``hot_mass`` of the probability uniformly on the first
    ``ceil(hot_frac · B)`` objects. All draws come from ONE
    ``np.random.default_rng(seed)`` in a fixed order, so streams are fully
    reproducible from (spec, seed).
    """

    objects: int
    nodes: int
    rounds: int
    ops_per_node: int = 1
    dist: str = "zipf"
    zipf: float = 1.0
    hot_frac: float = 0.1
    hot_mass: float = 0.9
    mix: Tuple[OpKind, ...] = RETWIS_MIX
    seed: int = 0

    def __post_init__(self):
        if min(self.objects, self.nodes, self.rounds, self.ops_per_node) < 1:
            raise ValueError("objects/nodes/rounds/ops_per_node must be >= 1")
        if self.dist not in DISTS:
            raise ValueError(f"unknown dist {self.dist!r}; one of {DISTS}")
        if self.dist == "hotset" and not (0 < self.hot_frac <= 1
                                          and 0 <= self.hot_mass <= 1):
            raise ValueError("hotset needs 0 < hot_frac <= 1, "
                             "0 <= hot_mass <= 1")
        if not self.mix or any(k.prob < 0 for k in self.mix):
            raise ValueError("mix must be non-empty with prob >= 0")
        if sum(k.prob for k in self.mix) <= 0:
            raise ValueError("mix probabilities must not all be zero")

    # -- distributions -------------------------------------------------------

    def object_probs(self) -> np.ndarray:
        """Per-object targeting probabilities [B], float64, sums to 1."""
        b = self.objects
        if self.dist == "zipf":
            ranks = np.arange(1, b + 1, dtype=np.float64)
            probs = ranks ** -self.zipf
        elif self.dist == "uniform":
            probs = np.ones(b, np.float64)
        else:                                            # hotset
            hot = max(int(np.ceil(self.hot_frac * b)), 1)
            probs = np.full(b, (1.0 - self.hot_mass) / max(b - hot, 1),
                            np.float64)
            probs[:hot] = self.hot_mass / hot
            if hot == b:                                 # all hot
                probs[:] = 1.0 / b
        return probs / probs.sum()

    def kind_probs(self) -> np.ndarray:
        p = np.asarray([k.prob for k in self.mix], np.float64)
        s = p.sum()
        # Renormalizing an already-normalized vector would perturb the
        # sampling cdf by ULPs and (with vanishing probability) change a
        # seeded draw — reproducibility of historical streams beats
        # cosmetic exactness, so only fix genuinely unnormalized mixes.
        return p if abs(s - 1.0) <= 1e-9 else p / s

    # -- streams -------------------------------------------------------------

    def streams(self) -> Tuple[np.ndarray, np.ndarray]:
        """Draw the raw schedule: ``(targets, kinds)``, both [T, N, K].

        Call order is part of the contract (targets first, then kinds, one
        rng) — changing it would silently change every seeded benchmark.
        """
        rng = np.random.default_rng(self.seed)
        shape = (self.rounds, self.nodes, self.ops_per_node)
        targets = rng.choice(self.objects, size=shape, p=self.object_probs())
        kinds = rng.choice(len(self.mix), size=shape, p=self.kind_probs())
        return targets, kinds

    def update_counts(self) -> np.ndarray:
        """Dense update-count table [T, N, B] int32: how many updates node
        n applies to object b in round t (reads contribute nothing)."""
        targets, kinds = self.streams()
        upd = np.zeros((self.rounds, self.nodes, self.objects), np.int32)
        per_kind = np.asarray([k.updates for k in self.mix], np.int32)
        tt, nn, _ = np.indices(targets.shape)
        np.add.at(upd, (tt, nn, targets), per_kind[kinds])
        return upd


def retwis(objects: int, nodes: int, rounds: int, ops_per_node: int,
           zipf: float, seed: int = 0) -> WorkloadSpec:
    """The paper's Retwis macro-benchmark shape (§V-D, Table II)."""
    return WorkloadSpec(objects=objects, nodes=nodes, rounds=rounds,
                        ops_per_node=ops_per_node, dist="zipf", zipf=zipf,
                        mix=RETWIS_MIX, seed=seed)


def retwis_weights(objects: int) -> np.ndarray:
    """Per-object element byte weights [B]: object classes cycle
    follower-set / wall / timeline (paper sizes 20B / 301B / 39B)."""
    return np.asarray([FOLLOW_B, WALL_B, TL_B], np.float64)[
        np.arange(objects) % 3]


@dataclasses.dataclass(frozen=True, eq=False)
class OpStream:
    """An op stream whose arrays are operands, not constants.

    ``apply(operands, x, t) -> deltas`` is the pure op program; it closes
    over no array, and equal ``apply`` values compute the same function of
    their operands (a frozen dataclass of static parameters, say).
    ``operands`` is a pytree of device arrays. Called as ``op_fn(x, t)``
    it is ``apply(operands, x, t)``, so it serves wherever an op_fn does.

    ``simulate_store`` passes the operands to its jitted chunk program as
    arguments and keys the program on ``apply`` and the operands' shapes
    and dtypes (DESIGN.md §16): a later call with other operands of the
    same shapes reuses the compiled program, and the program's cache
    holds ``apply`` but never the operands.
    """

    apply: Callable[[Any, Any, jnp.ndarray], Any]
    operands: Any

    def __call__(self, x, t):
        return self.apply(self.operands, x, t)


@dataclasses.dataclass(frozen=True)
class _SlotBump:
    """``versioned_slot_op``'s program over its [T, B, N] count table."""

    slots: int

    def __call__(self, operands, x, t):
        (upd,) = operands
        assert x.shape[0] == upd.shape[1], (
            f"count table built for {upd.shape[1]} objects cannot serve "
            f"{x.shape[0]} object rows — under shard=True the op sees "
            "device-local blocks; use a shard-aware op_fn")
        slots = self.slots
        cnt = upd[t]                                   # [B, N]
        ver = jnp.max(x, axis=-1, keepdims=True)       # [B, N, 1]
        idx = (ver % slots).astype(jnp.int32)
        sel = (jnp.arange(slots)[None, None, :] - idx) % slots \
            < cnt[..., None]
        return jnp.where(sel, x + 1, 0)


def versioned_slot_op(counts: np.ndarray, slots: int) -> OpStream:
    """Store op stream over versioned-slot objects (the Retwis model: each
    object is a ``MapLattice(slots, max_int)``).

    ``counts`` [T, N, B]: per-(round, node, object) update counts. Each
    node bumps ``cnt`` slots of the object starting at a rotating index
    derived from the object's current version — concurrent updates from
    different nodes hit overlapping slots, which is exactly the contention
    the paper's Zipf workload creates. Returns an op_fn over stacked
    states [B, N, slots] for ``simulate_store`` / ``simulate_sweep``.
    Rounds past T read the table's last round (the index clamps); the
    simulator gates quiet rounds' deltas to ⊥.

    The op_fn is an :class:`OpStream`: its one operand is the count table,
    transposed to [T, B, N] on the device, and its program depends on
    ``slots`` alone. ``simulate_store`` passes the table to its chunk
    program as an argument, so stores over different tables of one shape
    (other seeds, say) share one compiled program.

    The count table is indexed by the GLOBAL object axis, so device-local
    blocks (``simulate_store(shard=True)``) are not supported here — a
    sharded store needs an op_fn whose per-object data shards with ``x``
    (same contract as :func:`gset_unique_sweep_op`).
    """
    upd = jnp.asarray(np.transpose(np.asarray(counts), (0, 2, 1)))  # [T,B,N]
    return OpStream(_SlotBump(slots), (upd,))


def versioned_slot_cell_op(counts: np.ndarray, obj: int,
                           slots: int) -> Callable:
    """Single-object equivalent of :func:`versioned_slot_op` cell ``obj``
    (an op_fn over [N, slots] states for per-object ``simulate()`` runs —
    the store bit-identity baseline and the per-object-loop benchmark)."""
    upd = jnp.asarray(np.asarray(counts)[:, :, obj])       # [T, N]

    def op_fn(x, t):
        cnt = upd[t]                                       # [N]
        ver = jnp.max(x, axis=-1, keepdims=True)
        idx = (ver % slots).astype(jnp.int32)
        sel = (jnp.arange(slots)[None, :] - idx) % slots < cnt[:, None]
        return jnp.where(sel, x + 1, 0)

    return op_fn


@dataclasses.dataclass(frozen=True)
class _LwwWrites:
    """``lww_write_op``'s program over its four op tables."""

    keys: int

    def __call__(self, operands, x, t):
        with jax.named_scope("lww_writes"):
            upd, obj, key, val = operands
            ts = x[0]                                  # [B, N, keys]
            assert ts.shape[-1] == self.keys, (
                f"op stream built for {self.keys} keys cannot serve "
                f"objects of {ts.shape[-1]}")
            assert len(x) == 1 + val.shape[-1], (
                f"op stream of {val.shape[-1]} value planes cannot serve "
                f"points of {len(x)} planes")
            rounds, n, w = upd.shape
            # int32 throughout: the simulator traces its scan under x64,
            # where an unpinned index or clock would be emulated int64.
            t = jnp.minimum(jnp.asarray(t, jnp.int32), rounds - 1)
            node = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[:, None],
                                    (n, w))
            slot = jnp.arange(w, dtype=jnp.int32)[None, :]
            stamp = 2 + (t * n + node) * w + slot      # unique per write
            write = upd[t] != 0                        # [N, W]; reads: none
            o, k = obj[t], key[t]
            zero = jnp.zeros(ts.shape, jnp.int32)
            dt = zero.at[o, node, k].max(jnp.where(write, stamp,
                                                   jnp.int32(0)))
            # The newest write of a (node, key) in the round carries its
            # value planes: timestamps are unique, so exactly one write
            # wins, and the others scatter out of bounds.
            won = write & (dt[o, node, k] == stamp)
            o = jnp.where(won, o, jnp.int32(ts.shape[0]))
            return (dt,) + tuple(
                zero.at[o, node, k].set(val[t][..., j], mode="drop")
                for j in range(val.shape[-1]))


def lww_write_op(tables, keys: int) -> OpStream:
    """Store op stream of keyed last-writer-wins writes over LWW-map
    objects (``LWWMap(keys, width)``: (t, v₁, …, v_width) points of
    [B, N, keys] planes).

    ``tables``: ``(is_update, object, key, value)``, the first three
    [T, N, W] int32 and ``value`` [T, N, W, width] (or [T, N, W] for one
    value plane): op slot w of node n in active round t writes the record
    ``value`` under ``key`` of ``object`` where ``is_update`` is nonzero; a
    read writes nothing. The write's timestamp is ``2 + (t·N + n)·W + w`` —
    unique, ordered by round, then node, then slot (the stand-in for a
    clock ordered by time, then node address), and above 1, the timestamp
    of a loaded store's start state. A node's δ in a round is the lex join
    of its writes: the newest write of each key.

    The op_fn is an :class:`OpStream` over the four tables (device
    arrays, operands of the store's compiled program); rounds past T read
    the last round (the simulator gates quiet rounds' deltas to ⊥). Like
    :func:`versioned_slot_op` it indexes the GLOBAL object axis, so it
    does not serve ``simulate_store(shard=True)``.
    """
    if len(tables) != 4:
        raise ValueError("lww_write_op needs four tables (is_update, "
                         "object, key, value)")
    shapes = {np.shape(a) for a in tables[:3]}
    vshape = np.shape(tables[3])
    if len(shapes) != 1 or len(next(iter(shapes))) != 3 or \
            vshape[:3] != next(iter(shapes)) or len(vshape) not in (3, 4):
        raise ValueError("lww_write_op needs three [T, N, W] tables "
                         "(is_update, object, key) and a [T, N, W(, width)] "
                         "value table")
    rounds, n, w = next(iter(shapes))
    if 2 + rounds * n * w > np.iinfo(np.int32).max:
        raise ValueError(f"{rounds} x {n} x {w} writes overflow the int32 "
                         f"timestamps")
    value = np.asarray(tables[3])
    ops = tuple(jnp.asarray(np.asarray(a), jnp.int32) for a in tables[:3]) \
        + (jnp.asarray(value.reshape(value.shape[:3] + (-1,)), jnp.int32),)
    return OpStream(_LwwWrites(keys), ops)


def rotating_slot_op(nodes: int, slots: int) -> Callable:
    """Table-free store op stream over versioned-slot objects: each round
    every node bumps one (round, node)-derived slot of every object. The
    object extent comes from ``x``, so the same op drives a store whose
    object axis is sharded across devices (``simulate_store(shard=True)``),
    which :func:`versioned_slot_op` cannot."""

    def op_fn(x, t):
        rows = jnp.arange(nodes)
        slot = (t * 5 + rows) % slots
        cur = x[:, rows, slot]
        return jnp.zeros_like(x).at[:, rows, slot].set(cur + 1)

    return op_fn


# ---------------------------------------------------------------------------
# Table I micro-benchmark streams (Fig 7–10 harnesses, benchmarks/common.py)
# ---------------------------------------------------------------------------

def seed_perm(events: int, seed: int) -> np.ndarray:
    """The sweep-engine seed convention: seed 0 is the identity permutation
    (the paper-canonical stream); other seeds permute which unique element
    lands each round."""
    if seed == 0:
        return np.arange(events)
    return np.random.default_rng(seed).permutation(events)


def gset_unique_op(nodes: int, events: int, seed: int = 0) -> Callable:
    """Table I GSet: addition of a globally unique element per node/tick,
    in ``seed``'s permuted order. Single-run op_fn over [N, N·events]."""
    perm = jnp.asarray(seed_perm(events, seed), jnp.int32)

    def op_fn(x, t):
        ids = jnp.arange(nodes) * events + perm[jnp.minimum(t, events - 1)]
        d = jnp.zeros((nodes, nodes * events), jnp.bool_)
        return d.at[jnp.arange(nodes), ids].set(True)

    return op_fn


def gset_unique_sweep_op(nodes: int, events: int,
                         seeds: Sequence[int]) -> Callable:
    """Batched variant: cell b runs ``seeds[b]``'s permutation. The seed
    table is indexed by the GLOBAL batch (exact match, or a single seed
    broadcast to every cell) — device-local blocks (``shard=True``) need a
    natively sharded op_fn instead."""
    perms = jnp.asarray(np.stack([seed_perm(events, s) for s in seeds]),
                        jnp.int32)                      # [S, T]

    def op_fn(x, t):
        b = x.shape[0]
        assert b == len(seeds) or len(seeds) == 1, (
            f"op stream built for {len(seeds)} seeds cannot serve a "
            f"batch of {b} cells — pass exactly one seed (broadcast) or "
            "one per cell")
        tab = perms if len(seeds) == b \
            else jnp.broadcast_to(perms, (b,) + perms.shape[1:])
        tc = jnp.minimum(t, events - 1)
        ids = jnp.arange(nodes)[None, :] * events \
            + tab[:, tc][:, None]                      # [B, N]
        d = jnp.zeros((b, nodes, nodes * events), jnp.bool_)
        return d.at[jnp.arange(b)[:, None], jnp.arange(nodes)[None, :],
                    ids].set(True)

    return op_fn


def gcounter_op(nodes: int) -> Callable:
    """Table I GCounter: one increment per node/tick."""

    def op_fn(x, t):
        idx = jnp.arange(nodes)
        d = jnp.zeros((nodes, nodes), jnp.int32)
        return d.at[idx, idx].set(x[idx, idx] + 1)

    return op_fn


def gcounter_sweep_op(nodes: int) -> Callable:
    """Batched GCounter increments (deterministic — every cell identical)."""

    def op_fn(x, t):
        b = x.shape[0]
        idx = jnp.arange(nodes)
        d = jnp.zeros((b, nodes, nodes), jnp.int32)
        return d.at[:, idx, idx].set(x[:, idx, idx] + 1)

    return op_fn


def gmap_key_blocks(nodes: int, keys: int, k_pct: int) -> np.ndarray:
    """Table I GMap K%: disjoint per-node key blocks such that K% of all
    keys change per interval; block widths are clamped to the per-node
    span so rounding never makes them overlap (an overlap would create
    cross-node version contention the paper's benchmark doesn't have).
    Returns bool [N, keys]."""
    span = keys // nodes
    per_node = min(max(int(round(keys * k_pct / 100.0 / nodes)), 1), span)
    blocks = np.zeros((nodes, keys), bool)
    for i in range(nodes):
        start = i * span
        blocks[i, start:start + per_node] = True
    return blocks


def gmap_block_op(nodes: int, keys: int, k_pct: int) -> Callable:
    """Table I GMap K%: each node bumps the versions of its key block."""
    blocks = jnp.asarray(gmap_key_blocks(nodes, keys, k_pct))

    def op_fn(x, t):
        return jnp.where(blocks, x + 1, 0).astype(x.dtype)

    return op_fn
