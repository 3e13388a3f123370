"""Synchronization algorithms (paper §IV, Algorithms 1 & 2).

Implemented flavors:

* ``state``    — state-based full-state sync (baseline)
* ``classic``  — classic delta-based, Algorithm 1 (Almeida et al.)
* ``bp``       — + avoid back-propagation of δ-groups (origin tags)
* ``rr``       — + remove redundant state in received δ-groups (Δ-extract)
* ``bprr``     — Algorithm 2 (BP + RR), the paper's contribution
* ``state``/``classic``/… all share one synchronous-round step under scan.

Buffer representation (DESIGN.md §3): entries with equal origin are kept
joined in an origin-indexed slot ``B[N, P+1, ...]`` (slot P = local ops).
This is exact w.r.t. what Algorithm 2 sends — the per-neighbor send is a
join over entries filtered by origin, and join is associative/commutative —
while per-entry *sizes* are tracked in a separate counter for the memory
metric (the classic algorithm's buffer really holds every entry).

The per-neighbor send for BP flavors is a leave-one-out join across slots.
``loo="prefix"`` computes all P sends in O(P·U) via prefix/suffix joins
(beyond-paper optimization, EXPERIMENTS.md §Perf); ``loo="naive"`` is the
direct O(P²·U) fold for comparison.

Engines (DESIGN.md §11): ``engine="reference"`` runs the pure-jnp per-slot
receive loop below; ``engine="fused"`` executes the whole receive phase in
one Pallas kernel pass and the leave-one-out sends in one ``buffer_fold``
pass, with automatic fallback to the reference path for lattices without a
dense kernel kind. Both engines are bit-identical in states and metrics.

Faults (DESIGN.md §12): ``round_step`` optionally takes one round's
``RoundFaults`` masks (message loss / partitions / node churn compiled by
``sync/faults.py``). Down nodes send and receive nothing; undelivered
sends leave the sender's δ-buffer *retained* for retransmission instead of
cleared. With no faults (or all-ok masks) behavior is bit-identical to the
fault-free algorithm.

Sweeps (DESIGN.md §13): setting ``batch=B`` prepends a config axis to every
carry leaf ([B, N, ...U] states, [B, N, P+1, ...U] buffers) and makes
``round_step`` execute B independent simulations of the same algorithm over
the shared topology in one program; metrics come back per-config ([B]
instead of scalar). Every cell is bit-identical to the corresponding
unbatched run — all per-cell arithmetic is elementwise or reduces over the
same axes in the same order. The keyed object store (DESIGN.md §15) rides
the same axis with B = objects; ``batch_layout`` picks how the fused
kernels tile it ("grid" per-config grid dim for a few big configs,
"rows" flattened into tile rows for many small objects — bit-identical).

Anti-entropy resync (DESIGN.md §14): the delta flavors above only ship
δ-groups born from δ-mutations — a replica whose *state* diverged (fresh
join, healed partition) receives nothing from them. Two digest-era modes
close that gap, both pipelined into the same one-send-per-round step:

* ``state_driven``   — per edge, the lower-id endpoint ships its full
  state every round; the responder replies with the optimal
  Δ(its state, received state) computed at receive time (paper §VI /
  arXiv:1603.01529's state-driven sync). Half the full-state traffic of
  ``state``, optimal in the return direction.
* ``digest_driven``  — every node ships a block digest of its state
  (sync/digest.py) each round and, per neighbor, the blocks whose
  summaries disagree with that neighbor's last digest — near-optimal
  for arbitrary divergence at block granularity (ConflictSync,
  arXiv:2505.01144). Digest messages are priced as Merkle descents.

Neither mode retains δ-buffers: requests repeat every round, so loss,
partitions, and churn merely delay the next handshake (stale digests are
safe under monotone growth — see DESIGN.md §14).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.lattice import Lattice
from repro.sync import digest as dgst
from repro.sync import engine as engine_mod
from repro.sync import treeops as T
from repro.sync.digest import DigestSpec
from repro.sync.topology import Topology

ALGORITHMS = ("state", "classic", "bp", "rr", "bprr", "state_driven",
              "digest_driven")
# The digest-era anti-entropy modes (DESIGN.md §14); they take the resync
# round path instead of the Algorithm 1/2 δ-buffer path.
RESYNC_ALGORITHMS = ("state_driven", "digest_driven")


def metric_dtype():
    """Accumulator dtype for round metrics (DESIGN.md §10): int64 when x64
    is enabled (``simulate`` enables it around the scan so fleet-scale
    universe × degree × rounds products can't wrap), else the int32 the
    platform gives us."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


class RoundMetrics(NamedTuple):
    tx: jnp.ndarray        # elements sent this round (scalar; [B] batched)
    mem: jnp.ndarray       # elements held (state + buffer entries) at round end
    cpu: jnp.ndarray       # element-ops processed this round (proxy, DESIGN.md §10)
    max_mem_node: jnp.ndarray  # worst single-node memory


class AlgoCarry(NamedTuple):
    x: Any                 # [N, ...U] lattice states ([B, N, ...U] batched)
    buf: Any               # None | [(B,) N, ...U] | [(B,) N, P(+1), ...U]
    buf_elems: jnp.ndarray  # [(B,) N] buffered entry elements (memory metric)
    aux: Any = None        # algorithm round-trip state (digest_driven: the
                           # per-slot remote digests + validity flags)


@dataclasses.dataclass(frozen=True)
class SyncAlgorithm:
    name: str
    lattice: Lattice
    topo: Topology
    loo: str = "prefix"    # leave-one-out strategy for BP sends
    engine: str = "reference"  # "reference" | "fused" | "mega" (§11/§17)
    batch: Optional[int] = None  # config-axis width B, None = single run
                                 # (sweep engine, DESIGN.md §13)
    digest: Optional[DigestSpec] = None  # digest geometry for
                                         # "digest_driven" (None = default)
    batch_layout: str = "grid"   # fused-kernel tiling of the batch axis:
                                 # "grid" = per-config batch grid dim
                                 # (sweeps, §13); "rows" = flatten
                                 # (batch, node) into the tile row axis
                                 # (object stores, §15). Bit-identical.

    @property
    def resolved_engine(self) -> str:
        """Requested engine after the dense-kernel fallback."""
        return engine_mod.resolve(self.engine, self.lattice,
                                  resync=self.is_resync)

    @property
    def is_resync(self) -> bool:
        """Anti-entropy resync modes (DESIGN.md §14)."""
        return self.name in RESYNC_ALGORITHMS

    @property
    def digest_spec(self) -> DigestSpec:
        return self.digest if self.digest is not None else DigestSpec()

    @property
    def has_buffer(self) -> bool:
        # digest_driven holds digests (in aux), not δ-groups; state_driven's
        # buf holds the per-neighbor Δ-responses awaiting their send round.
        return self.name not in ("state", "digest_driven")

    @property
    def per_origin(self) -> bool:
        return self.name in ("bp", "bprr")

    @property
    def extracts(self) -> bool:
        return self.name in ("rr", "bprr")

    @property
    def batched(self) -> bool:
        return self.batch is not None

    @property
    def node_prefix(self) -> tuple:
        """Leading batch axes of a per-node array: (N,) or (B, N)."""
        n = self.topo.num_nodes
        return (n,) if self.batch is None else (self.batch, n)

    @property
    def slot_axis(self) -> int:
        """Axis of the origin slot in per-origin buffers."""
        return 1 if self.batch is None else 2

    def _msum(self, v, acc=None):
        """Metric sum over node/slot axes, preserving the config axis."""
        axes = tuple(range(1 if self.batched else 0, v.ndim))
        return jnp.sum(v if acc is None else v.astype(acc), axis=axes)

    # -- state ---------------------------------------------------------------

    def init(self, x0=None) -> AlgoCarry:
        p = self.topo.max_degree
        bot = self.lattice.bottom()
        prefix = self.node_prefix
        x = T.bcast(bot, prefix) if x0 is None else x0
        aux = None
        if self.name == "digest_driven":
            u = dgst.state_universe(bot)    # rejects undigestable lattices
            nb = self.digest_spec.num_blocks(u)
            buf = None
            # per-slot last-received remote digests + have-one flags
            aux = (jnp.zeros(prefix + (p, nb, dgst.CHANNELS), jnp.uint32),
                   jnp.zeros(prefix + (p,), jnp.bool_))
        elif self.name == "state_driven":
            buf = T.bcast(bot, prefix + (p,))   # destination-indexed resp
        elif not self.has_buffer:
            buf = None
        elif self.per_origin:
            buf = T.bcast(bot, prefix + (p + 1,))
        else:
            buf = T.bcast(bot, prefix)
        return AlgoCarry(x=x, buf=buf,
                         buf_elems=jnp.zeros(prefix, jnp.int32), aux=aux)

    # -- helpers ---------------------------------------------------------------

    def _loo_sends(self, buf):
        """d[i, p] = ⊔ {B[i, o] | o ≠ p} for p in 0..P-1 (slot P always in)."""
        lat = self.lattice
        p = self.topo.max_degree
        ax = self.slot_axis
        if self.resolved_engine in engine_mod.KERNEL_ENGINES:
            # one buffer_fold kernel pass over [P+1, (B·)N·U] (DESIGN.md §11)
            return engine_mod.fused_loo_sends(buf, kind=lat.kernel_kind,
                                              batched=self.batched,
                                              layout=self.batch_layout)
        slots = [T.slot(buf, k, axis=ax) for k in range(p + 1)]
        if self.loo == "naive":
            outs = []
            for j in range(p):
                acc = None
                for o in range(p + 1):
                    if o == j:
                        continue
                    acc = slots[o] if acc is None else lat.join(acc, slots[o])
                outs.append(acc)
        else:
            # prefix/suffix joins: O(P) joins for all P outputs. The ⊥
            # accumulator stays [N, ...U] even for sweeps — the first real
            # slot join broadcasts it up to the (possibly device-local)
            # config extent, keeping this closure shard-agnostic.
            bot = T.bcast(self.lattice.bottom(), (self.topo.num_nodes,))
            prefix = [None] * (p + 1)
            suffix = [None] * (p + 1)
            acc = bot
            for k in range(p + 1):
                prefix[k] = acc
                acc = lat.join(acc, slots[k])
            acc = bot
            for k in range(p, -1, -1):
                suffix[k] = acc
                acc = lat.join(acc, slots[k])
            outs = [lat.join(prefix[j], suffix[j]) for j in range(p)]
        # stack to [(B,) N, P, ...]
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=ax), *outs)

    # -- one synchronous round -------------------------------------------------

    def round_step(self, carry: AlgoCarry, op_delta, faults=None,
                   recv_counts: bool = False, want_inbox: bool = False):
        """One synchronous round; ``faults`` is an optional per-round
        ``faults.RoundFaults`` mask triple (None ⇒ fault-free; leaves carry
        a leading [B] axis when ``batch`` is set).

        Returns ``(carry, metrics)``; with ``recv_counts=True`` (the
        telemetry layer, DESIGN.md §18) a third element ``(recv, novel)``
        — per-node int32 received / novel-at-join element tallies summed
        over the P receive slots, identical across engines (the kernel
        engines reuse the kernels' ``cnt``/``dsz`` outputs, the reference
        loop re-derives them per slot). With ``want_inbox=True`` (the
        provenance replay, DESIGN.md §19) the LAST element is the
        active-masked inbox [(B,) N, P, ...U] — per receive slot, exactly
        the δ-group the slot-order fold consumed, ⊥ where topology padding
        or a fault suppressed it; bit-identical across engines. The
        default path is textually unchanged, which keeps
        ``telemetry=None``/``provenance=None`` bit-identical.
        """
        if self.is_resync:
            return self._resync_round(carry, op_delta, faults,
                                      recv_counts=recv_counts,
                                      want_inbox=want_inbox)
        lat, topo = self.lattice, self.topo
        p = topo.max_degree
        sax = self.slot_axis
        x, buf, buf_elems, _ = carry

        acc = metric_dtype()

        if self.resolved_engine == "mega":
            # Single-launch megakernel round (DESIGN.md §17): phases (1)-(4)
            # execute inside one kernels.round_step pallas_call; the engine
            # epilogue reuses the kernel's exact per-(node, slot) counts, so
            # the metric arithmetic below is shared verbatim.
            x, buf, buf_elems, tx, cpu, state_elems, recv, inbox = \
                engine_mod.mega_round(self, x, buf, buf_elems, op_delta,
                                      acc, faults=faults,
                                      want_recv=recv_counts,
                                      want_inbox=want_inbox)
            with jax.named_scope("round_metrics"):
                node_mem = state_elems.astype(acc) + buf_elems.astype(acc)
                metrics = RoundMetrics(
                    tx=tx,
                    mem=jnp.sum(node_mem, axis=-1),
                    cpu=cpu,
                    max_mem_node=jnp.max(node_mem, axis=-1),
                )
            out = AlgoCarry(x=x, buf=buf, buf_elems=buf_elems)
            ret = (out, metrics)
            ret += (recv,) if recv_counts else ()
            ret += (inbox,) if want_inbox else ()
            return ret

        cpu = jnp.zeros((), acc)

        # (1) local update: δ = mᵟ(xᵢ); store(δ, i)      [Alg 2, lines 6-8]
        dsz = lat.size(op_delta).astype(jnp.int32)             # [(B,) N]
        x = lat.join(x, op_delta)
        if self.has_buffer:
            if self.per_origin:
                self_slot = T.slot(buf, p, axis=sax)
                buf = T.set_slot(buf, p, lat.join(self_slot, op_delta),
                                 axis=sax)
            else:
                buf = lat.join(buf, op_delta)
            buf_elems = buf_elems + dsz
        cpu = cpu + self._msum(dsz, acc)

        # (2) sends                                        [Alg 2, lines 9-12]
        if not self.has_buffer:
            d_all = self._bcast_sends(x)
        elif self.per_origin:
            d_all = self._loo_sends(buf)
        else:
            d_all = self._bcast_sends(buf)
        send_sizes = lat.size(d_all).astype(jnp.int32)          # [(B,) N, P]
        # tx counts what an up sender puts on the wire, delivered or not
        # (DESIGN.md §12) — down nodes send nothing.
        send_live = topo.mask if faults is None \
            else topo.mask & faults.up[..., None]
        send_sizes = send_sizes * send_live
        tx = self._msum(send_sizes, acc)
        cpu = cpu + tx  # serialization cost ∝ elements sent

        # (3) clear buffer                                 [Alg 2, line 13]
        # Under faults, a node whose sends were not all delivered RETAINS
        # its buffer (ack-gated eviction) and re-sends next round; RR makes
        # the retransmission cheap at receivers that already saw it.
        if self.has_buffer:
            zeros = jax.tree.map(jnp.zeros_like, buf)
            if faults is None:
                buf = zeros
                buf_elems = jnp.zeros_like(buf_elems)
            else:
                delivered = jnp.all(faults.send_ok | ~topo.mask, axis=-1) \
                    & faults.up
                buf = T.where(delivered, zeros, buf)
                buf_elems = jnp.where(delivered, 0, buf_elems)

        # (4) receive all messages, sequentially per slot  [Alg 2, lines 14-17]
        if self.resolved_engine == "fused":
            x, buf, buf_elems, cpu, recv, inbox = engine_mod.fused_receive(
                self, x, buf, buf_elems, cpu, d_all, acc, faults=faults,
                want_recv=recv_counts, want_inbox=want_inbox)
        else:
            x, buf, buf_elems, cpu, recv, inbox = self._receive_reference(
                x, buf, buf_elems, cpu, d_all, acc, faults=faults,
                want_recv=recv_counts, want_inbox=want_inbox)

        # (5) metrics
        with jax.named_scope("round_metrics"):
            state_elems = lat.size(x).astype(jnp.int32)         # [(B,) N]
            node_mem = state_elems.astype(acc) + buf_elems.astype(acc)
            metrics = RoundMetrics(
                tx=tx,
                mem=jnp.sum(node_mem, axis=-1),
                cpu=cpu,
                max_mem_node=jnp.max(node_mem, axis=-1),
            )
        out = AlgoCarry(x=x, buf=buf, buf_elems=buf_elems)
        ret = (out, metrics)
        ret += (recv,) if recv_counts else ()
        ret += (inbox,) if want_inbox else ()
        return ret

    def _bcast_sends(self, state):
        """Broadcast one per-node state over the P send slots:
        [(B,) N, ...U] -> [(B,) N, P, ...U]."""
        p = self.topo.max_degree
        ax = self.slot_axis

        def bc(a):
            e = jnp.expand_dims(a, ax)
            return jnp.broadcast_to(e, a.shape[:ax] + (p,) + a.shape[ax:])

        return jax.tree.map(bc, state)

    # -- anti-entropy resync rounds (DESIGN.md §14) ----------------------------

    def _slot_where(self, cond, a, b):
        """Select between two slot-indexed states by a [(B,) N, P] mask.
        Like ``treeops.where_bot``, the mask grows one trailing singleton
        per universe axis (taken from the unbatched ⊥ leaf ranks) and then
        broadcasts right-aligned over any leading config axes — the
        closure never bakes in the config extent (shard-agnostic,
        DESIGN.md §13)."""

        def sel(xl, yl, bl):
            c = cond.reshape(cond.shape + (1,) * jnp.ndim(bl))
            return jnp.where(c, xl, yl)

        return jax.tree.map(sel, a, b, self.lattice.bottom())

    def _join_inbox(self, x, inbox, want_novel: bool = False):
        """x ⊔ every (pre-masked) inbox slot — the kernel pass of the
        resync receive. The reference loop and the fused ``round_recv``
        fold are bit-identical (max/or joins are exact). With
        ``want_novel`` (telemetry, DESIGN.md §18) also returns the
        per-node novel-element tally |Δ(slot, x_running)| summed over
        slots — the kernels' ``cnt`` output, or an extra Δ+size pass per
        slot on the reference path."""
        if self.resolved_engine in engine_mod.KERNEL_ENGINES:
            return engine_mod.fused_join_inbox(self, x, inbox,
                                               want_novel=want_novel)
        lat = self.lattice
        novel = None
        for q in range(self.topo.max_degree):
            d = T.slot(inbox, q, axis=self.slot_axis)
            if want_novel:
                sz = lat.size(lat.delta(d, x)).astype(jnp.int32)
                novel = sz if novel is None else novel + sz
            x = lat.join(x, d)
        return (x, novel) if want_novel else x

    def _resync_round(self, carry: AlgoCarry, op_delta, faults=None,
                      recv_counts: bool = False, want_inbox: bool = False):
        """One pipelined anti-entropy round for ``state_driven`` /
        ``digest_driven`` (DESIGN.md §14).

        Both modes are stateless w.r.t. δ-history: what a node sends is a
        function of its current state and (for responses) the most recent
        request/digest it holds, recomputed every round. Loss, partitions,
        and churn therefore need no ack-gated retention — a lost message
        is subsumed by the next handshake, and stale digests are safe
        because states only grow (skipping a block whose summaries matched
        at any past time never hides novelty the peer still lacks).
        """
        lat, topo = self.lattice, self.topo
        n, p = topo.num_nodes, topo.max_degree
        x, buf, buf_elems, aux = carry

        acc = metric_dtype()

        # (1) local update: δ = mᵟ(xᵢ) joins in (no buffering — resync
        # modes carry op effects inside the state itself)
        dsz = lat.size(op_delta).astype(jnp.int32)             # [(B,) N]
        x = lat.join(x, op_delta)
        cpu = self._msum(dsz, acc)

        up = None if faults is None else faults.up
        send_live = topo.mask if up is None else topo.mask & up[..., None]
        valid = topo.mask if faults is None else topo.mask & faults.recv_ok

        if self.name == "state_driven":
            # Per-edge orientation: the lower id initiates (ships state),
            # the higher id responds with Δ computed at receive time.
            ids = jnp.arange(n, dtype=topo.nbrs.dtype)
            init_send = (ids[:, None] < topo.nbrs) & topo.mask  # [N, P]
            req_recv = (topo.nbrs < ids[:, None]) & topo.mask
            d_all = self._slot_where(init_send, self._bcast_sends(x), buf)
            dig_words = None
        else:
            # digest_driven: every slot ships (digest, differing blocks).
            dig, dvalid = aux
            spec = self.digest_spec
            kind = lat.kernel_kind or "max"
            u = dgst.state_universe(lat.bottom())
            if self.resolved_engine in engine_mod.KERNEL_ENGINES:
                local_dig = engine_mod.fused_digest(
                    x, spec, kind, batched=self.batched,
                    layout=self.batch_layout)
            else:
                local_dig = dgst.digest_state(x, spec, kind)  # [.., N, nB, 3]
            local_exp = local_dig[..., None, :, :]            # slot bcast
            blocks = dgst.digest_diff(local_exp, dig) \
                & dvalid[..., None]                           # [.., N, P, nB]
            if self.resolved_engine in engine_mod.KERNEL_ENGINES:
                d_all = engine_mod.fused_extract(
                    x, blocks, spec, batched=self.batched,
                    layout=self.batch_layout)
            else:
                em = dgst.block_mask_to_elems(blocks, u, spec)
                d_all = dgst.extract_blocks(self._bcast_sends(x), em)
            # Digest exchange priced as the interactive Merkle-descent
            # transcript between the two CURRENT trees (root first, recurse
            # into differing subtrees — converged peers pay one root node),
            # capped at the flat leaf layer (a heavy-divergence descent
            # visits more nodes than just shipping every leaf). An
            # undelivered exchange costs the unanswered root only.
            dig_in = local_dig[:, topo.nbrs] if self.batched \
                else local_dig[topo.nbrs]                  # [.., N, P, nB, 3]
            flat = jnp.int32(spec.words(u))
            ok = topo.mask if faults is None else topo.mask & faults.send_ok
            desc = jnp.minimum(dgst.descent_words(local_exp, dig_in), flat)
            dig_words = jnp.where(ok, desc,
                                  jnp.int32(dgst.CHANNELS)) * send_live

        # (2) sends: tx counts what an up sender puts on the wire,
        # delivered or not (DESIGN.md §12)
        send_sizes = lat.size(d_all).astype(jnp.int32) * send_live
        tx = self._msum(send_sizes, acc)
        if dig_words is not None:
            tx = tx + self._msum(dig_words, acc)
        cpu = cpu + tx

        # (3) receive: gather + mask once in jnp (the masked inbox is also
        # the Δ-response / size operand), then one join fold per engine
        inbox = T.gather2(d_all, topo.nbrs, topo.rev, batched=self.batched)
        inbox = T.where_bot(valid, inbox, lat.bottom())
        recv_sizes = lat.size(inbox).astype(jnp.int32)         # [.., N, P]
        cpu = cpu + self._msum(recv_sizes, acc)
        if recv_counts:
            # Telemetry (DESIGN.md §18): received payload elements and the
            # novel subset at join time. Digest/descent words are metadata,
            # not state payload — excluded from the redundancy tallies.
            x, novel = self._join_inbox(x, inbox, want_novel=True)
            recv = (jnp.sum(recv_sizes, axis=-1), novel)
        else:
            x = self._join_inbox(x, inbox)
            recv = None

        if self.name == "state_driven":
            # (4a) responses: Δ(x', request) for every delivered request,
            # overwriting the response buffer (soft state — a lost request
            # just skips this round's response; the initiator re-requests)
            req_ok = req_recv & valid
            resp = T.where_bot(req_ok,
                               lat.delta(self._bcast_sends(x), inbox),
                               lat.bottom())
            rsz = lat.size(resp).astype(jnp.int32)             # [.., N, P]
            cpu = cpu + self._msum(rsz, acc)
            buf = resp
            buf_elems = jnp.sum(rsz, axis=-1).astype(jnp.int32)
        else:
            # (4b) store delivered digests (each sender broadcast ONE
            # digest to all its neighbors — no rev routing needed)
            dig = jnp.where(valid[..., None, None], dig_in, dig)
            dvalid = dvalid | valid
            aux = (dig, dvalid)
            # digesting the state is one elementwise pass over U per up node
            upm = jnp.ones_like(dsz) if up is None \
                else up.astype(jnp.int32) * jnp.ones_like(dsz)
            cpu = cpu + self._msum(upm * jnp.int32(u), acc)
            # memory: the stored remote digests are this mode's metadata
            buf_elems = (jnp.sum(dvalid, axis=-1)
                         * jnp.int32(spec.words(u))).astype(jnp.int32)

        # (5) metrics
        state_elems = lat.size(x).astype(jnp.int32)            # [(B,) N]
        node_mem = state_elems.astype(acc) + buf_elems.astype(acc)
        metrics = RoundMetrics(
            tx=tx,
            mem=jnp.sum(node_mem, axis=-1),
            cpu=cpu,
            max_mem_node=jnp.max(node_mem, axis=-1),
        )
        out = AlgoCarry(x=x, buf=buf, buf_elems=buf_elems, aux=aux)
        ret = (out, metrics)
        ret += (recv,) if recv_counts else ()
        # The resync inbox is built masked once above — it IS the
        # provenance view (responses/extractions ride the same slots).
        ret += (inbox,) if want_inbox else ()
        return ret

    def _receive_reference(self, x, buf, buf_elems, cpu, d_all, acc,
                           faults=None, want_recv: bool = False,
                           want_inbox: bool = False):
        """Reference receive: sequential per-slot jnp loop (3+ HBM passes
        over the state per slot — the fused engine's baseline). The fifth
        return is the telemetry ``(recv, novel)`` per-node tally pair
        (DESIGN.md §18) or None; the sixth the stacked masked inbox
        [(B,) N, P, ...U] when ``want_inbox`` (provenance, DESIGN.md §19)
        or None; with both flags off the emitted program is unchanged."""
        lat, topo = self.lattice, self.topo
        p = topo.max_degree
        sax = self.slot_axis
        recv_n = novel_n = None
        slots = []
        for q in range(p):
            sender = topo.nbrs[:, q]
            sslot = topo.rev[:, q]
            valid = topo.mask[:, q]
            if faults is not None:
                valid = valid & faults.recv_ok[..., q]
            d = T.gather2(d_all, sender, sslot,
                          batched=self.batched)                 # [(B,) N, ...U]
            # where_bot: valid may be [N] (no faults) against [B, N, ...U]
            # leaves and leaf universe ranks differ (linear-sum tags are
            # rank-0) — per-leaf ⊥-aligned select keeps the closure shard-
            # agnostic (the local config extent never appears in it).
            d = T.where_bot(valid, d, lat.bottom())
            if want_inbox:
                slots.append(d)
            if want_recv:
                dsz_q = lat.size(d).astype(jnp.int32)           # [(B,) N]
                recv_n = dsz_q if recv_n is None else recv_n + dsz_q

            if self.name == "state":
                if want_recv:
                    nv = lat.size(lat.delta(d, x)).astype(jnp.int32)
                    novel_n = nv if novel_n is None else novel_n + nv
                cpu = cpu + self._msum(lat.size(d), acc)
                x = lat.join(x, d)
                continue

            if self.extracts:
                stored = lat.delta(d, x)                        # RR: Δ(d, xᵢ)
                keep = jnp.logical_not(lat.is_bottom(stored)) & valid
            else:
                stored = d                                      # classic: whole group
                keep = jnp.logical_not(lat.leq(d, x)) & valid   # inflation check

            ssz = lat.size(stored).astype(jnp.int32) * keep
            if want_recv:
                # RR's extraction IS Δ(d, x_running), so its size doubles
                # as the novelty tally; classic/bp pay one extra Δ+size.
                nv = ssz if self.extracts \
                    else lat.size(lat.delta(d, x)).astype(jnp.int32)
                novel_n = nv if novel_n is None else novel_n + nv
            cpu = cpu + self._msum(lat.size(d), acc) + self._msum(ssz, acc)
            x = lat.join(x, d)
            if self.per_origin:
                cur = T.slot(buf, q, axis=sax)
                upd = T.where(keep, lat.join(cur, stored), cur)
                buf = T.set_slot(buf, q, upd, axis=sax)
            else:
                buf = T.where(keep, lat.join(buf, stored), buf)
            buf_elems = buf_elems + ssz
        recv = (recv_n, novel_n) if want_recv else None
        inbox = jax.tree.map(lambda *ls: jnp.stack(ls, axis=sax), *slots) \
            if want_inbox else None
        return x, buf, buf_elems, cpu, recv, inbox
