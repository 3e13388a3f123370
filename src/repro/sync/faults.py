"""Fault injection for the synchronous-round simulator (DESIGN.md §12).

The paper's evaluation (§V) runs lossless static-membership rounds, but
deltas exist *because* real networks drop messages and nodes churn (Almeida
et al., arXiv:1603.01529). A ``FaultSchedule`` models the three failure
modes every later scenario composes from:

* **message loss**   — per-directed-edge Bernoulli drops,
* **partitions**     — deterministic windows cutting all edges across a
                       node grouping,
* **node churn**     — down/up windows (``runtime/membership.py``-style
                       epochs: piecewise-constant down-sets).

All three compile to two dense boolean tables, built once on the host and
threaded through ``lax.scan`` as per-round slices — the simulated program
stays a single jitted scan with masking only, no Python-level branching:

* ``link_ok[T, N, P]`` — delivery of the directed message arriving at node
  ``n``'s receive slot ``q`` in round ``t`` (receiver-slot view; each
  (round, receiver, slot) triple IS one directed message),
* ``up[T, N]``         — node liveness per round.

Fault semantics (honored identically by both engines, DESIGN.md §12):

* a *down* node executes no ops, sends nothing, receives nothing; its
  state and δ-buffer are frozen (crash-recovery with durable state — the
  monotone model matching membership's suspect-don't-remove design);
* ``tx`` counts every element an *up* node puts on the wire, delivered or
  not — loss is paid for, which is exactly what the fault benchmark
  measures;
* a node whose sends were not all delivered in a round **retains** its
  δ-buffer instead of clearing it (the synchronous-round analogue of
  ack-gated buffer eviction in delta-CRDT transports) and re-sends it next
  round. Receivers that already saw the data RR-extract it to ⊥, so BP+RR
  pays almost nothing for retransmission while classic delta re-floods —
  without retention, a dropped δ-group would be lost forever and no delta
  algorithm could converge.

With an all-ok schedule every mask is identity, so results are bit-equal
to the schedule-free simulator.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.sync.topology import Topology


class RoundFaults(NamedTuple):
    """One round's fault masks, as carried inside the scan."""

    recv_ok: jnp.ndarray   # bool [N, P] — message into slot (n, q) delivered
    send_ok: jnp.ndarray   # bool [N, P] — send on (n, q)'s edge delivered
    up: jnp.ndarray        # bool [N]


class FaultViews(NamedTuple):
    """Whole-run fault masks, the scan's xs ([T, N, P] / [T, N]).

    ``recv_ok``/``send_ok`` are fully folded: a message is delivered iff
    the link is up AND both endpoints are up. ``send_ok[i, j]`` is the
    sender-side view of the same delivery bit (``recv_ok`` re-indexed
    through ``nbrs``/``rev``), so both sides of an edge agree.
    """

    recv_ok: jnp.ndarray
    send_ok: jnp.ndarray
    up: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class FaultSchedule:
    """Per-round fault tables bound to one topology (host-side numpy)."""

    topo: Topology
    link_ok: np.ndarray    # bool [T, N, P], receiver-slot view
    up: np.ndarray         # bool [T, N]

    def __post_init__(self):
        t, n, p = self.link_ok.shape
        assert (n, p) == (self.topo.num_nodes, self.topo.max_degree)
        assert self.up.shape == (t, n)

    @property
    def num_rounds(self) -> int:
        return self.link_ok.shape[0]

    @property
    def is_trivial(self) -> bool:
        mask = np.asarray(self.topo.mask)
        return bool(self.link_ok[:, mask].all() and self.up.all())

    @property
    def last_fault_round(self) -> int:
        """Last round with any fault, or -1 for an all-ok schedule."""
        mask = np.asarray(self.topo.mask)
        faulty = ~self.link_ok[:, mask].all(axis=-1) | ~self.up.all(axis=-1)
        hits = np.nonzero(faulty)[0]
        return int(hits[-1]) if hits.size else -1

    # -- constructors --------------------------------------------------------

    @staticmethod
    def none(topo: Topology, rounds: int) -> "FaultSchedule":
        n, p = topo.num_nodes, topo.max_degree
        return FaultSchedule(
            topo=topo,
            link_ok=np.ones((rounds, n, p), bool),
            up=np.ones((rounds, n), bool),
        )

    @staticmethod
    def bernoulli(topo: Topology, rounds: int, rate: float,
                  seed: int = 0) -> "FaultSchedule":
        """IID per-directed-message loss at ``rate`` (each valid (round,
        receiver, slot) triple is one directed message)."""
        n, p = topo.num_nodes, topo.max_degree
        rng = np.random.default_rng(seed)
        drop = rng.random((rounds, n, p)) < rate
        sched = FaultSchedule.none(topo, rounds)
        link = sched.link_ok & ~(drop & np.asarray(topo.mask)[None])
        return dataclasses.replace(sched, link_ok=link)

    @staticmethod
    def partition(topo: Topology, rounds: int, start: int, stop: int,
                  groups: Sequence[int]) -> "FaultSchedule":
        """Cut every edge whose endpoints lie in different ``groups`` during
        rounds ``[start, stop)`` — a deterministic network partition."""
        groups = np.asarray(groups)
        assert groups.shape == (topo.num_nodes,)
        nbrs = np.asarray(topo.nbrs)
        cross = groups[:, None] != groups[nbrs]            # [N, P]
        window = np.zeros((rounds, 1, 1), bool)
        window[start:stop] = True
        sched = FaultSchedule.none(topo, rounds)
        link = sched.link_ok & ~(window & cross[None])
        return dataclasses.replace(sched, link_ok=link)

    @staticmethod
    def churn(topo: Topology, rounds: int,
              down_windows: Sequence[Tuple[int, int, int]]) -> "FaultSchedule":
        """Node down/up epochs: ``down_windows`` is a sequence of
        ``(node, start, stop)`` — node is down during ``[start, stop)``."""
        sched = FaultSchedule.none(topo, rounds)
        up = sched.up.copy()
        for node, start, stop in down_windows:
            up[start:stop, node] = False
        return dataclasses.replace(sched, up=up)

    @staticmethod
    def from_epochs(topo: Topology, rounds: int,
                    epochs: Sequence[Tuple[int, Sequence[int]]]
                    ) -> "FaultSchedule":
        """Churn from ``runtime/membership.py``-style epochs: a
        piecewise-constant timeline ``[(start_round, down_set), ...]`` —
        each epoch's down-set holds until the next epoch begins (the shape
        an ``ElasticPlan`` sequence produces). Rounds before the first
        epoch have everyone up."""
        sched = FaultSchedule.none(topo, rounds)
        up = sched.up.copy()
        ordered = sorted(epochs, key=lambda e: e[0])
        for i, (start, down) in enumerate(ordered):
            stop = ordered[i + 1][0] if i + 1 < len(ordered) else rounds
            for node in down:
                up[start:stop, node] = False
        return dataclasses.replace(sched, up=up)

    def same_topology(self, topo: Topology) -> bool:
        """Structural match — name alone can collide for ad-hoc
        ``_from_adj`` graphs, so compare the neighbor tables too."""
        return (self.topo.name == topo.name
                and np.array_equal(np.asarray(self.topo.nbrs),
                                   np.asarray(topo.nbrs))
                and np.array_equal(np.asarray(self.topo.mask),
                                   np.asarray(topo.mask)))

    def compose(self, other: "FaultSchedule") -> "FaultSchedule":
        """Intersection of two schedules over the same topology (shorter
        schedule padded with all-ok)."""
        assert self.same_topology(other.topo), \
            "schedules bound to different topologies"
        t = max(self.num_rounds, other.num_rounds)
        a, b = self._padded(t), other._padded(t)
        return FaultSchedule(
            topo=self.topo,
            link_ok=a.link_ok & b.link_ok,
            up=a.up & b.up,
        )

    def _padded(self, rounds: int) -> "FaultSchedule":
        t = self.num_rounds
        if t >= rounds:
            return self
        n, p = self.topo.num_nodes, self.topo.max_degree
        pad_l = np.ones((rounds - t, n, p), bool)
        pad_u = np.ones((rounds - t, n), bool)
        return FaultSchedule(
            topo=self.topo,
            link_ok=np.concatenate([self.link_ok, pad_l]),
            up=np.concatenate([self.up, pad_u]),
        )

    # -- scan inputs ---------------------------------------------------------

    def views(self, total_rounds: int) -> FaultViews:
        """Fold node liveness into per-edge delivery and derive the sender
        view; pad with all-ok up to ``total_rounds`` (rounds past the
        schedule are fault-free — the "eventually connected" tail)."""
        s = self._padded(total_rounds)
        nbrs = np.asarray(self.topo.nbrs)
        rev = np.asarray(self.topo.rev)
        link_ok = s.link_ok[:total_rounds]
        up = s.up[:total_rounds]
        sender_up = up[:, nbrs]                            # [T, N, P]
        recv_ok = link_ok & sender_up & up[:, :, None]
        send_ok = recv_ok[:, nbrs, rev]                    # sender's view
        return FaultViews(
            recv_ok=jnp.asarray(recv_ok),
            send_ok=jnp.asarray(send_ok),
            up=jnp.asarray(up),
        )

    # -- host-side queries (gossip runtime / examples) -----------------------

    def up_at(self, t: int, node: int) -> bool:
        if t >= self.num_rounds:
            return True
        return bool(self.up[t, node])

    def delivers(self, t: int, src: int, dst: int) -> bool:
        """Delivery of the directed message src → dst at round ``t``
        (folds link state and both endpoints' liveness). Non-edges of the
        topology never deliver, at any round."""
        nbrs = np.asarray(self.topo.nbrs)[dst]
        mask = np.asarray(self.topo.mask)[dst]
        slots = np.nonzero((nbrs == src) & mask)[0]
        if slots.size == 0:
            return False
        if t >= self.num_rounds:
            return True
        if not (self.up[t, src] and self.up[t, dst]):
            return False
        return bool(self.link_ok[t, dst, slots[0]])

    def drop_fn(self, clock):
        """A ``LocalTransport.drop_fn`` driven by this schedule; ``clock``
        is a zero-arg callable returning the current round."""
        return lambda src, dst: not self.delivers(clock(), src, dst)
