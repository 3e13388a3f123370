"""Keyed object-store engine: B independent CRDT objects as ONE program
(DESIGN.md §15, §16).

The paper's flagship macro-benchmark (§V-D Retwis, Figs 11–12) is a
*store*: many independent CRDT objects — follower GSets, wall/timeline
maps — each synchronized per-object under Zipf contention. Every object
is its own little simulation (own δ-buffers, own inflation checks, own
digest state), but they all share one lattice shape, one algorithm, and
one cluster topology — which is exactly the shape the sweep engine's
config axis (DESIGN.md §13) batches. This module rides that machinery
with **B = number of objects**:

* states stack to [B, N, ...U], origin buffers to [B, N, P+1, ...U],
  digest aux to [B, N, P, nB, 3]; the scan body is the same
  ``build_round_step`` program ``simulate`` runs, so **every store cell
  is bit-identical (states and all metrics) to a standalone per-object
  ``simulate()``** on both engines (``tests/test_store.py``);
* unlike a sweep, the *network* is shared: one optional
  ``FaultSchedule`` applies to every object simultaneously (a partition
  partitions the whole store). Its masks ride the scan as [T, 1, N, P]
  views — a singleton object axis that broadcasts, instead of the
  sweep's per-cell [T, B, N, P] stacks (O(T·N·P) memory, not O(T·B·N·P));
* metrics come back per-object ([B, T]) with store-level aggregates and
  **weighted element accounting**: per-object byte weights (Retwis's
  31 B ids / 270 B tweets / 20 B user ids) turn element counts into byte
  metrics inside the engine instead of benchmark-side numpy math;
* the fused engine runs the object axis in the kernels' ``rows`` layout
  (object × node flattened into the tile row axis) — millions of small
  objects tile into a few large kernel launches instead of B tiny grid
  steps — and the object axis shards across devices via
  ``launch.mesh.shard_store_scan`` (the ("object", "config") store
  mesh; objects never communicate).

Memory-bounded scale-out (DESIGN.md §16) stacks three independent knobs
on top:

* ``chunk_rounds=k`` runs the scan in time chunks with the carry
  DONATED between chunks and per-chunk metrics offloaded to host, so
  peak device memory is O(store shard + chunk) instead of O(store × T);
* ``object_metrics=False`` reduces the per-object [B] round metrics to
  per-shard partial sums INSIDE the scan body (exact — the accumulators
  are integers), shrinking the metric ys from O(B·T) to O(T);
* ``checkpoint=...`` wires ``checkpoint/checkpointer.py`` into the
  chunk boundaries — carry + metrics-so-far are saved every chunk, and
  ``resume_store`` restores a bundle and continues **bit-identically**
  (same final states, same metrics as the uninterrupted run).

Arbitrary object counts shard by padding: the object axis is padded to
the device multiple with ⊥-state objects that receive no ops, and the
pad is masked out of every result (sliced off per-object views, masked
out of in-scan reductions).

Workload generators for the store live in ``sync/workloads.py``.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
from pathlib import Path
from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.lattice import BatchWeights, Lattice
from repro.obs import provenance as prv
from repro.obs import telemetry as obs
from repro.obs.trace import maybe_span
from repro.sync import engine as engine_mod
from repro.sync.algorithms import (RESYNC_ALGORITHMS, RoundMetrics,
                                   SyncAlgorithm, metric_dtype)
from repro.sync.digest import DigestSpec
from repro.sync.faults import FaultSchedule, FaultViews
from repro.sync.simulator import (
    SimResult,
    build_round_step,
    collect_result,
    first_stable_round,
    metric_context,
    run_scan_chunked,
    scan_program,
)
from repro.sync.topology import Topology
from repro.sync.workloads import OpStream

LAYOUTS = ("rows", "grid")


@dataclasses.dataclass(frozen=True)
class StoreSpec:
    """The ingredients of one store run.

    ``op_fn(x, t) -> deltas`` sees the stacked states ([B, N, ...U]; the
    object axis leads) and returns stacked deltas — per-object op streams
    live in the object axis (see ``workloads.versioned_slot_op``). An
    ``op_fn`` that is a ``workloads.OpStream`` hands its tables to the
    program as operands, which lets ``simulate_store`` reuse one compiled
    program across calls (see there). Under
    object-axis padding on an unsplit axis the op_fn sees exactly the
    unpadded [objects, ...] states (the engine slices the pad off before
    calling and joins ⊥ rows back on); on a multi-device sharded axis it
    must be shard-agnostic — derive the object extent from ``x`` — and
    the engine masks the pad out of the results instead.

    ``weights``: optional per-object element byte weights [B] — every
    non-⊥ irreducible of object b is priced at ``weights[b]`` bytes in
    the ``*_bytes`` views of :class:`StoreResult`.

    ``x0``: optional stacked initial states [B, N, ...U] (None = all-⊥).
    The leading (object) axis of every leaf is validated eagerly here;
    the full [B, N, ...U] shape — and the op_fn's output structure — are
    validated by ``simulate_store`` before anything runs.

    ``faults``: one optional schedule for the WHOLE store — objects share
    the network, so a lost message, partition window, or down node hits
    every object in that round identically.
    """

    objects: int
    op_fn: Callable[[Any, jnp.ndarray], Any]
    weights: Optional[np.ndarray] = None
    x0: Any = None
    faults: Optional[FaultSchedule] = None

    def __post_init__(self):
        if self.objects < 1:
            raise ValueError(f"objects must be >= 1, got {self.objects}")
        if self.weights is not None:
            w = np.asarray(self.weights, np.float64)
            if w.shape != (self.objects,):
                raise ValueError(
                    f"weights must be [objects]=[{self.objects}], got "
                    f"shape {w.shape}")
            object.__setattr__(self, "weights", w)
        if self.x0 is not None:
            for leaf in jax.tree.leaves(self.x0):
                shape = tuple(np.shape(leaf))
                if len(shape) < 1 or shape[0] != self.objects:
                    raise ValueError(
                        f"StoreSpec.x0 must stack objects on the leading "
                        f"axis of every leaf: expected leading extent "
                        f"objects={self.objects}, got leaf shape {shape} — "
                        f"build x0 as [objects, nodes, ...universe] (e.g. "
                        f"jnp.stack of per-object [N, ...U] states)")

    def shared_views(self, topo: Topology,
                     total_rounds: int) -> Optional[FaultViews]:
        """Compile the store-wide schedule into scan xs with a singleton
        object axis: [T, 1, N, P] masks that broadcast over every object
        (vs the sweep's per-cell [T, B, N, P] stacks)."""
        if self.faults is None:
            return None
        if not self.faults.same_topology(topo):
            raise ValueError(
                f"StoreSpec.faults was built for topology "
                f"{self.faults.topo.name!r}, not {topo.name!r}")
        v = self.faults.views(total_rounds)
        return FaultViews(*(jnp.expand_dims(a, 1) for a in v))


class StoreResult(NamedTuple):
    """Per-object metrics plus store-level (optionally byte-weighted)
    aggregates. ``sim`` is the batched engine result: [B, T] metrics,
    [B, N, ...U] final states.

    With ``object_metrics=False`` the engine reduced the object axis
    inside the scan: ``sim`` holds per-shard partial sums ([S, T] with
    S = shard count) instead of per-object rows, the ``store_*``
    aggregates are exact (integer partial sums commute bit-for-bit with
    the host reduction), and the per-object views raise.
    """

    sim: SimResult
    weights: Optional[np.ndarray] = None          # [B] bytes per element
    final_state_bytes: Optional[np.ndarray] = None  # [B, N] weighted elems
    object_metrics: bool = True
    num_objects: Optional[int] = None

    # -- per-object views ----------------------------------------------------

    def _per_object(self, what: str):
        if not self.object_metrics:
            raise ValueError(
                f"{what} is a per-object view, but this run reduced the "
                f"object axis in-scan (object_metrics=False) — only the "
                f"store_* aggregates and final states are available; rerun "
                f"with object_metrics=True for per-object metrics")

    @property
    def objects(self) -> int:
        if self.num_objects is not None:
            return self.num_objects
        return self.sim.batch

    @property
    def tx(self) -> np.ndarray:          # [B, T]
        self._per_object("tx")
        return self.sim.tx

    @property
    def mem(self) -> np.ndarray:
        self._per_object("mem")
        return self.sim.mem

    @property
    def cpu(self) -> np.ndarray:
        self._per_object("cpu")
        return self.sim.cpu

    @property
    def max_mem_node(self) -> np.ndarray:
        self._per_object("max_mem_node")
        return self.sim.max_mem_node

    @property
    def uniform(self):
        self._per_object("uniform")
        return self.sim.uniform

    @property
    def final_x(self):
        return self.sim.final_x

    def object_result(self, b: int) -> SimResult:
        """Object b as a single-run SimResult — the view the store
        bit-identity invariant is stated over."""
        self._per_object("object_result")
        return self.sim.cell(b)

    @property
    def telemetry(self):
        """The run's ``obs.TelemetryResult`` (None unless requested):
        [B, T, N] per-object channels, or — with ``object_metrics=False``
        — [S, T, N] per-shard partials (sums for recv/novel/buf, maxes
        for stale/ack/gap; DESIGN.md §18)."""
        return self.sim.telemetry

    def convergence_round(self):
        """Per-object first round after which all nodes stayed identical
        ([B] int, −1 = never; needs ``track_convergence``)."""
        self._per_object("convergence_round")
        return self.sim.convergence_round()

    # -- store-level aggregates ----------------------------------------------
    # Work in both metric modes: summing per-object rows and summing the
    # in-scan per-shard partial sums are the same integer total.

    @property
    def store_tx(self) -> np.ndarray:    # [T] elements, all objects
        return self.sim.tx.sum(axis=0)

    @property
    def store_mem(self) -> np.ndarray:
        return self.sim.mem.sum(axis=0)

    @property
    def store_cpu(self) -> np.ndarray:
        return self.sim.cpu.sum(axis=0)

    @property
    def store_max_mem_node(self) -> np.ndarray:  # [T] worst node anywhere
        return self.sim.max_mem_node.max(axis=0)

    @property
    def total_cpu(self) -> int:
        return int(self.sim.cpu.sum())

    @property
    def store_uniform(self) -> Optional[np.ndarray]:
        """[T] bool: every object's cluster agreed at round end (None
        when convergence was not tracked)."""
        if self.sim.uniform is None:
            return None
        return np.all(np.asarray(self.sim.uniform, bool), axis=0)

    def store_convergence_round(self) -> int:
        """First round after which EVERY object's cluster stayed
        identical (−1 = never; needs ``track_convergence``). Available
        in both metric modes."""
        if self.sim.uniform is None:
            raise ValueError(
                "per-round convergence was not tracked; pass "
                "simulate_store(track_convergence=True)")
        return int(first_stable_round(self.store_uniform))

    # -- weighted (byte) accounting ------------------------------------------

    def _w(self) -> np.ndarray:
        if self.weights is None:
            raise ValueError(
                "no per-object weights — pass StoreSpec(weights=...)")
        return self.weights

    @property
    def tx_bytes(self) -> np.ndarray:    # [B, T]
        self._per_object("tx_bytes")
        return np.asarray(self.sim.tx, np.float64) * self._w()[:, None]

    @property
    def mem_bytes(self) -> np.ndarray:
        self._per_object("mem_bytes")
        return np.asarray(self.sim.mem, np.float64) * self._w()[:, None]

    @property
    def store_tx_bytes(self) -> np.ndarray:   # [T]
        return self.tx_bytes.sum(axis=0)

    @property
    def store_mem_bytes(self) -> np.ndarray:
        return self.mem_bytes.sum(axis=0)

    @property
    def total_tx_bytes(self) -> float:
        return float(self.store_tx_bytes.sum())


def _as_checkpointer(checkpoint) -> Optional[Checkpointer]:
    if checkpoint is None or isinstance(checkpoint, Checkpointer):
        return checkpoint
    return Checkpointer(checkpoint)


def _pad_tree(tree, bot, pad: int, lead_shape) -> Any:
    """Append ``pad`` ⊥ rows on the leading (object) axis of every leaf.
    ``lead_shape`` are the axes between object and universe (e.g. (N,))."""

    def f(leaf, b):
        leaf = jnp.asarray(leaf)
        row = jnp.broadcast_to(jnp.asarray(b),
                               (pad,) + tuple(lead_shape) + jnp.shape(b))
        return jnp.concatenate([leaf, row.astype(leaf.dtype)], axis=0)

    return jax.tree.map(f, tree, bot)


def _validate_x0(x0, lattice: Lattice, n: int, objects: int):
    """Full [B, N, ...U] shape check of a stacked initial state."""
    bot = lattice.bottom()
    s_x0 = jax.tree.structure(x0)
    s_bot = jax.tree.structure(bot)
    if s_x0 != s_bot:
        raise ValueError(
            f"StoreSpec.x0 tree structure {s_x0} does not match the "
            f"lattice state structure {s_bot}")
    for leaf, b in zip(jax.tree.leaves(x0), jax.tree.leaves(bot)):
        want = (objects, n) + tuple(np.shape(b))
        got = tuple(np.shape(leaf))
        if got != want:
            raise ValueError(
                f"StoreSpec.x0 leaf has shape {got} but this "
                f"{lattice.name!r} store over {n} nodes needs "
                f"[objects, nodes, ...universe] = {want}")


def _validate_op_fn(op_fn, x0, lattice: Lattice, n: int, objects: int):
    """Shape-trace op_fn against the stacked state BEFORE the scan runs:
    a mis-shaped delta would otherwise surface as an opaque scan/jit
    shape error (or worse, broadcast into wrong semantics)."""
    if x0 is not None:
        tmpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                           jnp.asarray(a).dtype), x0)
    else:
        tmpl = jax.tree.map(
            lambda b: jax.ShapeDtypeStruct(
                (objects, n) + tuple(np.shape(b)), jnp.asarray(b).dtype),
            lattice.bottom())
    t = jax.ShapeDtypeStruct((), jnp.int32)
    try:
        out = jax.eval_shape(op_fn, tmpl, t)
    except Exception as e:
        raise ValueError(
            f"StoreSpec.op_fn failed shape tracing against the stacked "
            f"state [objects={objects}, nodes={n}, ...universe]: {e}") from e
    if jax.tree.structure(out) != jax.tree.structure(tmpl):
        raise ValueError(
            f"StoreSpec.op_fn returned tree structure "
            f"{jax.tree.structure(out)} but the stacked state is "
            f"{jax.tree.structure(tmpl)} — op_fn must return one delta "
            f"leaf per state leaf")
    for o, x in zip(jax.tree.leaves(out), jax.tree.leaves(tmpl)):
        if tuple(o.shape) != tuple(x.shape):
            raise ValueError(
                f"StoreSpec.op_fn returned a delta leaf of shape "
                f"{tuple(o.shape)} for a state leaf of shape "
                f"{tuple(x.shape)} — deltas must match the stacked "
                f"[objects, nodes, ...universe] state exactly (per-object "
                f"op streams live in the leading object axis)")


def _validate_block_op_fn(op_fn, lattice: Lattice, n: int, block: int,
                          nshard: int):
    """Shape-trace op_fn against one DEVICE block of the sharded object
    axis: under ``shard_map`` the op stream runs per device, so it must
    derive the object extent from ``x`` (e.g. ``x.shape[0]``) instead of
    closing over global [B]-shaped tables."""
    tmpl = jax.tree.map(
        lambda bl: jax.ShapeDtypeStruct(
            (block, n) + tuple(np.shape(bl)), jnp.asarray(bl).dtype),
        lattice.bottom())
    try:
        out = jax.eval_shape(op_fn, tmpl, jax.ShapeDtypeStruct((), jnp.int32))
        ok = all(tuple(o.shape) == tuple(x.shape) for o, x in
                 zip(jax.tree.leaves(out), jax.tree.leaves(tmpl)))
        err = None
    except Exception as e:
        ok, err = False, e
    if not ok:
        raise ValueError(
            f"StoreSpec.op_fn cannot run on a sharded object axis: each "
            f"of the {nshard} devices scans its own block of {block} "
            f"objects, so op_fn must derive the object extent from "
            f"x (e.g. x.shape[0]) rather than closing over global "
            f"[objects]-shaped op tables"
            + (f" (block-shape trace failed with: {err})" if err else ""))


def _reduce_step(step, telemetry=None):
    """Wrap the round step to reduce the per-object metrics to ONE
    partial sum inside the scan body (DESIGN.md §16). ``omask`` rides the
    CARRY — never the closure — so under ``shard_map`` each device holds
    its own [B_pad/S] block of the mask and emits its own [1] partials
    (gathered to [S]); integer sums/maxes make the host-side total
    bit-identical to the per-object reduction. Padded objects are masked
    out here (a padded digest_driven object still pays the Merkle floor,
    so dropping rows after the fact would not be enough).

    With ``telemetry`` the step's third ys entry (the [B, N] channels,
    DESIGN.md §18) reduces the same way — object-axis sums for the
    payload tallies, maxes for the lag/gap channels — re-emitted in the
    metric accumulator dtype so store-scale sums cannot wrap int32."""

    def wrapped(carry, xs):
        om, inner = carry
        if telemetry is None:
            inner, (m, uni) = step(inner, xs)
        else:
            inner, (m, uni, ch) = step(inner, xs)

        def red(v):
            return jnp.sum(jnp.where(om, v, 0), keepdims=True)

        with jax.named_scope("round_metrics"):
            metrics = RoundMetrics(
                tx=red(m.tx), mem=red(m.mem), cpu=red(m.cpu),
                max_mem_node=jnp.max(jnp.where(om, m.max_mem_node, 0),
                                     keepdims=True))
            uni = jnp.all(uni | ~om, keepdims=True)
        if telemetry is None:
            return (om, inner), (metrics, uni)

        mdt = metric_dtype()
        omn = om[:, None]                        # channels are [B, N]

        def rsum(v):
            return jnp.sum(jnp.where(omn, v.astype(mdt), 0), axis=0,
                           keepdims=True)

        def rmax(v):
            return jnp.max(jnp.where(omn, v.astype(mdt), 0), axis=0,
                           keepdims=True)

        ch = obs.TelemetryChannels(
            recv_elems=rsum(ch.recv_elems), novel_elems=rsum(ch.novel_elems),
            stale_rounds=rmax(ch.stale_rounds), ack_lag=rmax(ch.ack_lag),
            buf_elems=rsum(ch.buf_elems), div_gap=rmax(ch.div_gap))
        return (om, inner), (metrics, uni, ch)

    return wrapped


def simulate_store(
    algo: str,
    lattice: Lattice,
    topo: Topology,
    spec: StoreSpec,
    active_rounds: int,
    quiet_rounds: int = 0,
    loo: str = "prefix",
    jit: bool = True,
    engine: str = "reference",
    wide_metrics: bool = True,
    track_convergence: Optional[bool] = None,
    shard: bool = False,
    digest: Optional[DigestSpec] = None,
    layout: str = "rows",
    chunk_rounds: Optional[int] = None,
    checkpoint: Union[Checkpointer, str, Path, None] = None,
    object_metrics: bool = True,
    pad_to: Optional[int] = None,
    telemetry: Optional[obs.TelemetrySpec] = None,
    provenance: Optional[prv.ProvenanceSpec] = None,
    trace=None,
) -> StoreResult:
    """Run ``spec.objects`` independent CRDT objects of one
    ``algo`` × ``lattice`` × ``topo`` as one jitted scan.

    Semantics are ``simulate`` per object: ``res.object_result(b)`` is
    bit-identical to the single run with object b's op stream / initial
    state, under the store-shared fault schedule, on either ``engine``.

    ``layout`` picks the fused-engine kernel tiling for the object axis
    (DESIGN.md §15): ``"rows"`` flattens (object, node) into the tile row
    axis — the right shape for many small objects — while ``"grid"`` is
    the sweep engine's per-config batch grid dimension. Both are
    bit-identical; the reference engine ignores it.

    ``track_convergence`` defaults on exactly when a fault schedule is
    given.

    Compiled once per shape (DESIGN.md §16): where ``spec.op_fn`` is a
    ``workloads.OpStream`` (``versioned_slot_op`` is one), its operands
    are arguments of the jitted scan program, never constants, and the
    program is kept in a bounded LRU (``program_cache_info()``) under a
    key of everything that decides its trace: algorithm, engine, ``loo``,
    ``layout``, ``digest``, the lattice object, the topology's neighbour
    tables by content, the padded object count, ``active_rounds``, total
    rounds, ``chunk_rounds``, the metric modes, ``shard`` and the devices,
    the telemetry and provenance specs, the fault views' presence and
    shapes, the op stream's ``apply`` and its operands' shapes and
    dtypes. A later call with an equal key (another seed's count table of
    the same shape, say) skips tracing, lowering and the compile-cache
    read. The cache holds ``jax.jit`` wrappers and no operand, carry or
    result. A plain closure ``op_fn`` (or ``jit=False``) builds its
    program for the call alone, its tables constants as before.

    Scale knobs (DESIGN.md §16; all bit-identical to the plain run):

    * ``shard=True`` splits the object axis across the local device mesh
      (``launch.mesh.store_mesh``). Arbitrary object counts are padded
      to the shard multiple with ⊥ objects and the pad is masked out of
      every result. ``pad_to`` forces a specific pad multiple (mostly a
      test knob; must be compatible with the shard count).
    * ``chunk_rounds=k`` drives the scan in k-round chunks with the
      carry donated between chunks and metrics offloaded to host —
      peak device memory O(store + chunk) instead of O(store × T).
    * ``checkpoint=`` a ``Checkpointer`` (or directory path) saves
      carry + metrics-so-far at every chunk boundary (requires
      ``chunk_rounds``); ``resume_store`` continues bit-identically.
    * ``object_metrics=False`` reduces round metrics to per-shard
      partial sums inside the scan — O(T) metric memory instead of
      O(B·T); ``StoreResult.store_*`` aggregates stay exact, per-object
      views raise.

    Observability (DESIGN.md §18): ``telemetry=obs.TelemetrySpec()``
    attaches per-object [B, T, N] diagnostic channels (per-shard
    [S, T, N] partials under ``object_metrics=False``); ``trace`` takes
    an ``obs.TraceLog`` and records the call's host phases as spans
    under one ``store_call`` (``store_validate``, ``store_build``,
    ``store_scan`` with its ``chunk_dispatch``/``chunk_offload`` and
    ``checkpoint_save`` spans and ``chunk_boundary`` instants,
    ``store_collect``), which also label the host timeline when the call
    runs under ``jax.profiler``. ``provenance=prv.ProvenanceSpec()``
    attaches the per-object element-lineage trace (DESIGN.md §19) —
    per-element coverage/waste matrices are [B, N, E], so it requires
    ``object_metrics=True`` (the lineage matrices cannot be reduced to
    shard partials without losing the per-element views).
    """
    with maybe_span(trace, "store_call", algo=algo, engine=engine,
                    engine_ran=_engine_ran(algo, engine, lattice),
                    objects=spec.objects):
        return _simulate_store(
            algo, lattice, topo, spec, active_rounds, quiet_rounds, loo=loo,
            jit=jit, engine=engine, wide_metrics=wide_metrics,
            track_convergence=track_convergence, shard=shard, digest=digest,
            layout=layout, chunk_rounds=chunk_rounds, checkpoint=checkpoint,
            object_metrics=object_metrics, pad_to=pad_to,
            telemetry=telemetry, provenance=provenance, trace=trace,
            resume=None)


def resume_store(
    algo: str,
    lattice: Lattice,
    topo: Topology,
    spec: StoreSpec,
    active_rounds: int,
    quiet_rounds: int = 0,
    *,
    checkpoint: Union[Checkpointer, str, Path],
    step: Optional[int] = None,
    chunk_rounds: Optional[int] = None,
    loo: str = "prefix",
    jit: bool = True,
    engine: str = "reference",
    wide_metrics: bool = True,
    track_convergence: Optional[bool] = None,
    shard: bool = False,
    digest: Optional[DigestSpec] = None,
    layout: str = "rows",
    object_metrics: bool = True,
    pad_to: Optional[int] = None,
    telemetry: Optional[obs.TelemetrySpec] = None,
    provenance: Optional[prv.ProvenanceSpec] = None,
    trace=None,
) -> StoreResult:
    """Restore a chunk-boundary checkpoint and run the REMAINING rounds.

    Pass the same ``spec`` / config the interrupted ``simulate_store``
    ran with (the manifest's run fingerprint is verified and a mismatch
    raises before anything is restored — see ``Checkpointer.restore``
    for the bundle-integrity checks). ``step`` picks a specific saved
    round boundary (default: the newest); ``chunk_rounds`` defaults to
    the value recorded in the manifest. The completed result is
    bit-identical to the uninterrupted run — same final states, same
    metrics (``tests/test_store.py``). Checkpointing continues from the
    restored boundary, so a resumed run can itself be resumed.
    """
    ckpt = _as_checkpointer(checkpoint)
    steps = ckpt.available_steps()
    if not steps:
        raise ValueError(f"no checkpoints under {ckpt.dir}")
    if step is None:
        step = steps[-1]
    if step not in steps:
        raise ValueError(
            f"no checkpoint for round {step} under {ckpt.dir} — "
            f"available: {steps}")
    extra = ckpt.manifest(step).get("extra", {})
    if chunk_rounds is None:
        chunk_rounds = extra.get("chunk_rounds")
        if chunk_rounds is None:
            raise ValueError(
                f"checkpoint step {step} under {ckpt.dir} records no "
                f"chunk_rounds — pass chunk_rounds= explicitly")
    with maybe_span(trace, "store_call", algo=algo, engine=engine,
                    engine_ran=_engine_ran(algo, engine, lattice),
                    objects=spec.objects, resume_round=step):
        return _simulate_store(
            algo, lattice, topo, spec, active_rounds, quiet_rounds, loo=loo,
            jit=jit, engine=engine, wide_metrics=wide_metrics,
            track_convergence=track_convergence, shard=shard, digest=digest,
            layout=layout, chunk_rounds=chunk_rounds, checkpoint=ckpt,
            object_metrics=object_metrics, pad_to=pad_to,
            telemetry=telemetry, provenance=provenance, trace=trace,
            resume=(ckpt, step, extra))


def _engine_ran(algo: str, engine: str, lattice: Lattice) -> str:
    """The engine a call runs after the automatic jnp fallback
    (``engine.resolve``), recorded beside the one asked for."""
    return engine_mod.resolve(engine, lattice,
                              resync=algo in RESYNC_ALGORITHMS)


def _simulate_store(algo, lattice, topo, spec, active_rounds, quiet_rounds,
                    *, loo, jit, engine, wide_metrics, track_convergence,
                    shard, digest, layout, chunk_rounds, checkpoint,
                    object_metrics, pad_to, telemetry, provenance, trace,
                    resume) -> StoreResult:
    if layout not in LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; one of {LAYOUTS}")
    if provenance is not None and not object_metrics:
        raise ValueError(
            "provenance= requires object_metrics=True: lineage matrices "
            "are per-object [B, N, E] views and cannot be reduced to "
            "per-shard partial sums in-scan (DESIGN.md §19)")
    if chunk_rounds is not None and chunk_rounds < 1:
        raise ValueError(f"chunk_rounds must be >= 1, got {chunk_rounds}")
    ckpt = _as_checkpointer(checkpoint)
    if ckpt is not None and chunk_rounds is None:
        raise ValueError(
            "checkpoint= requires chunk_rounds: bundles are written at "
            "chunk boundaries (DESIGN.md §16)")
    b = spec.objects
    n = topo.num_nodes

    # -- eager validation (before any compile/alloc) -------------------------
    with maybe_span(trace, "store_validate"):
        if spec.x0 is not None:
            _validate_x0(spec.x0, lattice, n, b)
        _validate_op_fn(spec.op_fn, spec.x0, lattice, n, b)

    # -- object-axis padding geometry ----------------------------------------
    nshard = 1
    launch_mesh = None
    if shard:
        from repro.launch import mesh as launch_mesh
        nshard = launch_mesh.axis_shards(launch_mesh.store_mesh(),
                                         launch_mesh.STORE_AXIS)
    mult = nshard if pad_to is None else pad_to
    if mult < 1:
        raise ValueError(f"pad_to must be >= 1, got {pad_to}")
    b_pad = b + (-b) % mult                      # launch.mesh.padded_size
    if b_pad % nshard:
        raise ValueError(
            f"pad_to={pad_to} pads {b} objects to {b_pad}, which the "
            f"{nshard}-shard object mesh cannot split — use a multiple "
            f"of {nshard} (or drop pad_to and let the engine pad)")
    pad = b_pad - b

    if nshard > 1:
        # Sharded op_fns must derive the object extent from x itself
        # (shard_map hands them per-device blocks of b_pad/nshard
        # objects); a closure over global [B]-shaped op tables would
        # fail deep inside the mapped scan — catch it here instead.
        _validate_block_op_fn(spec.op_fn, lattice, n, b_pad // nshard,
                              nshard)

    x0 = spec.x0
    if pad and x0 is not None:
        x0 = _pad_tree(x0, lattice.bottom(), pad, (n,))
    elif x0 is not None and chunk_rounds is not None:
        # The chunk program donates its input carry: the caller's x0 must
        # outlive the call (a spec serves call after call).
        x0 = jax.tree.map(jnp.copy, x0)

    total = active_rounds + quiet_rounds
    with maybe_span(trace, "store_build") as counts:
        alg = SyncAlgorithm(name=algo, lattice=lattice, topo=topo, loo=loo,
                            engine=engine, batch=b_pad, digest=digest,
                            batch_layout=layout)
        carry0 = alg.init(x0)
        views = spec.shared_views(topo, total)
        if track_convergence is None:
            track_convergence = views is not None
        x_init = carry0.x
        if telemetry is not None:
            carry0 = (obs.init_carry(alg), carry0)
        if provenance is not None:
            carry0 = (prv.init_carry(provenance, alg, x_init), carry0)
        if not object_metrics:
            # The pad mask rides the carry (not the closure) so it shards
            # with P("object") like every other carry leaf.
            carry0 = (jnp.arange(b_pad) < b, carry0)
        if views is None:
            xs = jnp.arange(total)
        else:
            xs = (jnp.arange(total), views.recv_ok, views.send_ok, views.up)

        fp = _run_fingerprint(
            algo, engine, lattice, topo, layout, loo, b, b_pad, total,
            chunk_rounds, object_metrics, track_convergence, wide_metrics,
            shard, digest, telemetry, provenance, active_rounds, views)
        op, key = spec.op_fn, None
        if isinstance(op, OpStream):
            apply, operands = op.apply, op.operands
            if jit:
                key = _program_key(fp, lattice, apply, operands, shard)
        else:       # a closure: its tables are constants of this program
            apply, operands = (lambda _, x, t: op(x, t)), ()

        def build():
            wrap = None
            if shard:
                def wrap(run):
                    return launch_mesh.shard_store_scan(run, b_pad)
            return _chunk_program(
                alg, apply, b, pad if nshard == 1 else 0, active_rounds,
                views is not None, track_convergence, telemetry, provenance,
                object_metrics, wrap, jit, donate=chunk_rounds is not None)

        run, counts["program"] = _PROGRAMS.get(key, build)

    # -- resume: restore carry + metric prefix from the bundle ---------------
    start, ys_prefix = 0, None
    if resume is not None:
        ckpt_r, at, extra = resume
        bad = [k for k, v in fp.items() if extra.get(k) != v]
        if bad:
            detail = ", ".join(
                f"{k}: saved {extra.get(k)!r} vs requested {fp[k]!r}"
                for k in bad)
            raise ValueError(
                f"checkpoint round {at} under {ckpt_r.dir} was written by "
                f"a different store run — {detail}")
        if at > total:
            raise ValueError(
                f"checkpoint round {at} is past total rounds {total}")
        mdt = np.int64 if wide_metrics else np.int32
        sdim = b_pad if object_metrics else nshard
        ys_like = (RoundMetrics(tx=np.zeros((at, sdim), mdt),
                                mem=np.zeros((at, sdim), mdt),
                                cpu=np.zeros((at, sdim), mdt),
                                max_mem_node=np.zeros((at, sdim), mdt)),
                   np.zeros((at, sdim), bool))
        if telemetry is not None:
            cdt = np.int32 if object_metrics else mdt
            ys_like = ys_like + (obs.TelemetryChannels(
                *(np.zeros((at, sdim, n), cdt) for _ in range(6))),)
        if provenance is not None:
            # provenance requires object_metrics, so channels stay int32
            ys_like = ys_like + (prv.ProvChannels(
                *(np.zeros((at, sdim, n), np.int32) for _ in range(3))),)
        like = {"carry": carry0, "ys": ys_like}
        # int64 metric prefixes would silently downcast to int32 outside
        # the x64 context (jnp.asarray in restore).
        with metric_context(wide_metrics):
            bundle = ckpt_r.restore(at, like)
        carry0 = bundle["carry"]
        ys_prefix = jax.device_get(bundle["ys"])
        start = at

    # -- run -----------------------------------------------------------------
    with maybe_span(trace, "store_scan", algo=algo, engine=engine,
                    objects=b, rounds=total):
        if chunk_rounds is None:
            with metric_context(wide_metrics):
                carry, ys = run(carry0, xs, operands)
        else:
            on_chunk = None
            if ckpt is not None:

                def on_chunk(rounds_done, carry, ys_host):
                    with maybe_span(trace, "checkpoint_save",
                                    rounds_done=int(rounds_done)):
                        ckpt.save(rounds_done,
                                  {"carry": jax.device_get(carry),
                                   "ys": ys_host},
                                  extra=fp)

            carry, ys = run_scan_chunked(
                run, carry0, xs, wide_metrics, chunk_rounds,
                operands=operands, on_chunk=on_chunk, start=start,
                ys_prefix=ys_prefix, trace=trace)
    with maybe_span(trace, "store_collect") as counts:
        metrics, uniform = ys[0], ys[1]
        channels = ys[2] if telemetry is not None else None
        prov_channels = ys[-1] if provenance is not None else None
        if not object_metrics:
            _, carry = carry
        prov_carry = None
        if provenance is not None:
            prov_carry, carry = carry
        if telemetry is not None:
            _, carry = carry
        sim = collect_result(carry, metrics, uniform, track_convergence,
                             batched=True, telemetry=telemetry,
                             channels=channels, provenance=provenance,
                             prov_carry=prov_carry,
                             prov_channels=prov_channels, nbrs=topo.nbrs)

        # -- mask the pad back out --------------------------------------------
        if pad:
            fx = jax.tree.map(lambda a: a[:b], sim.final_x)
            if object_metrics:
                sim = sim._replace(
                    tx=sim.tx[:b], mem=sim.mem[:b], cpu=sim.cpu[:b],
                    max_mem_node=sim.max_mem_node[:b], final_x=fx,
                    uniform=None if sim.uniform is None else sim.uniform[:b],
                    telemetry=None if sim.telemetry is None
                    else sim.telemetry.take_lead(b),
                    provenance=None if sim.provenance is None
                    else sim.provenance.take_lead(b))
            else:
                sim = sim._replace(final_x=fx)   # metrics already pad-masked

        fsb = None
        if spec.weights is not None:
            # Weighted final-state footprint [B, N]: every irreducible of
            # object b priced at weights[b] bytes. BatchWeights aligns the
            # [B] vector against each leaf's own rank (mixed-rank lattices
            # broadcast per leaf — a single stacked reshape would not).
            fsb = np.asarray(
                lattice.wsize(sim.final_x,
                              BatchWeights(jnp.asarray(spec.weights))),
                np.float64)
        if trace is not None:
            # the final states and their footprint came from the device
            counts["bytes"] = sum(
                a.nbytes for a in jax.tree.leaves((sim.final_x, fsb)))
    return StoreResult(sim=sim, weights=spec.weights, final_state_bytes=fsb,
                       object_metrics=object_metrics, num_objects=b)


def _host_topology(topo: Topology) -> Topology:
    """``topo`` with its tables on the host: a kept program holds no
    device buffer of the call that built it."""
    return dataclasses.replace(topo, nbrs=np.asarray(topo.nbrs),
                               mask=np.asarray(topo.mask),
                               rev=np.asarray(topo.rev))


def _chunk_program(alg, apply, objects, pad, active_rounds, faulty,
                   track_convergence, telemetry, provenance, object_metrics,
                   wrap, jit, donate):
    """The store's scan program ``run(carry, xs, operands)``: the round
    step over the op program ``apply`` and its operand arguments.

    With ``pad`` ⊥ objects appended to an unsplit object axis the op sees
    exactly the ``objects`` real rows (op streams may hold [B]-shaped
    tables) and ⊥ deltas keep the pad rows at bottom forever. On a split
    axis (``pad=0`` here) each device holds a block, not a prefix, so the
    shard-agnostic op drives the pad rows like real objects and the
    results mask them out (objects never interact, so evolved pad rows
    are inert).

    Everything the program closes over is configuration: ``alg`` (with
    its topology tables copied to the host), ``apply``, the specs. The
    call's operands, carry and xs are arguments, so a kept program pins
    none of them.
    """
    alg = dataclasses.replace(alg, topo=_host_topology(alg.topo))
    n = alg.topo.num_nodes

    def step_of(operands):
        if pad:
            bot = alg.lattice.bottom()

            def op_fn(x, t):
                d = apply(operands, jax.tree.map(lambda a: a[:objects], x),
                          t)
                return _pad_tree(d, bot, pad, (n,))
        else:
            def op_fn(x, t):
                return apply(operands, x, t)

        step = build_round_step(alg, op_fn, active_rounds, faulty,
                                track_convergence, telemetry, provenance)
        if not object_metrics:
            step = _reduce_step(step, telemetry)
        return step

    return scan_program(step_of, jit, wrap, donate)


def _program_key(fp: dict, lattice: Lattice, apply, operands,
                 shard: bool):
    """Everything that decides the traced chunk program: the run's
    fingerprint (algorithm, engine, layout, topology tables by content,
    padding, rounds, chunking, metric modes, telemetry, provenance, fault
    views), the lattice (its functions by identity), the op program and
    its operands' structure, shapes and dtypes, and the devices a sharded
    store spans."""
    leaves, tree = jax.tree.flatten(operands)
    return (json.dumps(fp, sort_keys=True), lattice, apply, tree,
            tuple((tuple(np.shape(a)), str(a.dtype)) for a in leaves),
            tuple(d.id for d in jax.devices()) if shard else None)


class ProgramCacheInfo(NamedTuple):
    hits: int
    misses: int
    size: int
    maxsize: int


class _ProgramCache:
    """A bounded LRU of jitted store programs by ``_program_key``. It
    holds ``jax.jit`` wrappers only, so ``jax.clear_caches()`` frees their
    executables; a key of None (a closure op_fn, or ``jit=False``) builds
    a program for the call alone."""

    def __init__(self, maxsize: int):
        self.maxsize = maxsize
        self._programs = collections.OrderedDict()
        self.hits = self.misses = 0

    def get(self, key, build):
        """``(program, "hit" | "miss")``."""
        run = None if key is None else self._programs.get(key)
        if run is not None:
            self._programs.move_to_end(key)
            self.hits += 1
            return run, "hit"
        self.misses += 1
        run = build()
        if key is not None:
            self._programs[key] = run
            while len(self._programs) > self.maxsize:
                self._programs.popitem(last=False)
        return run, "miss"

    def info(self) -> ProgramCacheInfo:
        return ProgramCacheInfo(self.hits, self.misses, len(self._programs),
                                self.maxsize)

    def clear(self):
        self._programs.clear()
        self.hits = self.misses = 0


_PROGRAMS = _ProgramCache(maxsize=8)


def program_cache_info() -> ProgramCacheInfo:
    """Hits and misses of the store's program cache since the process
    started (or since ``clear_program_cache``), its size and bound. A
    miss is a call that built its chunk program; a hit reused one."""
    return _PROGRAMS.info()


def clear_program_cache():
    """Drop every kept store program and zero the counts."""
    _PROGRAMS.clear()


def _topology_digest(topo: Topology) -> str:
    h = hashlib.sha256()
    for a in (topo.nbrs, topo.mask, topo.rev):
        a = np.asarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _run_fingerprint(algo, engine, lattice, topo, layout, loo, objects,
                     padded, total_rounds, chunk_rounds, object_metrics,
                     track_convergence, wide_metrics, shard, digest,
                     telemetry, provenance, active_rounds, views) -> dict:
    """JSON-safe identity of a store run, written into every chunk
    checkpoint's manifest and verified on resume — restoring a bundle
    into a differently-configured run would type-check (same carry
    shapes for many configs) but break bit-identity silently. It is also
    the JSON part of the chunk program's cache key (``_program_key``)."""
    return {
        "kind": "store",
        "algo": algo,
        "engine": engine,
        "lattice": lattice.name,
        "topology": topo.name,
        "neighbours": _topology_digest(topo),
        "layout": layout,
        "loo": loo,
        "objects": objects,
        "padded": padded,
        "active_rounds": active_rounds,
        "total_rounds": total_rounds,
        "chunk_rounds": chunk_rounds,
        "object_metrics": bool(object_metrics),
        "track_convergence": bool(track_convergence),
        "wide_metrics": bool(wide_metrics),
        "shard": bool(shard),
        "digest": None if digest is None else dataclasses.asdict(digest),
        # Telemetry/provenance change the carry/ys pytrees, so a bundle
        # written with a different spec cannot restore into this run.
        "telemetry": None if telemetry is None else telemetry.asdict(),
        "provenance": None if provenance is None else provenance.asdict(),
        "faults": None if views is None
        else [list(np.shape(a)) for a in views],
    }
