"""State lattices, join decompositions, and optimal deltas (paper §III).

The paper's key objects:

* join-irreducible states  (Definition 1)
* irredundant join decomposition  ⇓x = maximals of irreducibles below x
  (Definition 3 / Proposition 2, via Birkhoff)
* optimal delta  Δ(a, b) = ⊔{y ∈ ⇓a | y ⋢ b}   with  Δ(a,b) ⊔ b = a ⊔ b
  and minimality  c ⊔ b = a ⊔ b ⇒ Δ(a,b) ⊑ c
* optimal δ-mutators  mᵟ(x) = Δ(m(x), x)

TPU adaptation (DESIGN.md §3): states are *dense fixed-universe* maps from a
static universe U to a value lattice. The join-irreducibles of such a map
lattice are the single-slot states, so ⇓x is represented implicitly by the
array itself and Δ becomes a fused elementwise select — exactly the shape of
computation the `kernels/` Pallas kernels tile for VMEM.

Everything here is pure-jnp and batch-friendly: all reductions are over the
trailing universe axis, so states may carry arbitrary leading batch axes
(e.g. the node axis of a simulated cluster).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.value_lattices import ValueLattice

Array = Any
State = Any  # a single array or tuple of arrays (struct-of-arrays points)


def _map_point(fn, state, *others):
    """Apply ``fn`` across the struct-of-arrays components of a point."""
    if isinstance(state, tuple):
        return tuple(fn(s, *(o[i] for o in others)) for i, s in enumerate(state))
    return fn(state, *others)


@dataclasses.dataclass(frozen=True)
class Lattice:
    """A state lattice with join-decomposition support.

    ``size`` counts non-bottom join-irreducibles — the paper's measurement
    unit ("number of elements / map entries") for transmission & memory.
    """

    name: str
    bottom: Callable[[], State]
    join: Callable[[State, State], State]
    leq: Callable[[State, State], Array]          # scalar (per batch) bool
    delta: Callable[[State, State], State]        # optimal Δ(a, b)
    size: Callable[[State], Array]                # #non-bottom irreducibles
    is_bottom: Callable[[State], Array]           # scalar (per batch) bool
    # Pointwise views (universe-axis resolution), used by RR and the kernels:
    irreducible_mask: Callable[[State], Array]    # bool[..., U]
    novel_mask: Callable[[State, State], Array]   # bool[..., U]: ⇓a slots ⋢ b
    # Dense-kernel dispatch (DESIGN.md §11/§17): the Pallas kernel kind that
    # implements this lattice's join/Δ on dense planes, or None if only the
    # pure-jnp reference engine applies (products, linear sums).
    #   "max"   — pointwise max order (ℕ-max entries; bool-or as 0/1 max)
    #   "bitor" — bit-packed sets, one irreducible per bit
    #   "lex"   — (t, v₁, …) tuples of planes under the lexicographic
    #             order (LWW maps; the megakernel alone implements it)
    kernel_kind: str | None = None
    # Weighted element accounting (DESIGN.md §15): wsize(x, w) sums ``w``
    # over x's non-bottom irreducibles instead of counting them — ``w``
    # broadcasts against the universe axis (per-slot weights, plain
    # right-aligned numpy broadcasting) or, wrapped in
    # :class:`BatchWeights`, against leading batch axes (e.g. per-object
    # byte weights in the keyed object store, where every element of
    # object b weighs w[b] bytes). Alignment happens per LEAF via
    # :func:`align_weights`: each leaf grows exactly the trailing
    # singleton axes its own rank needs, so product lattices whose
    # components carry different universe ranks (mixed-rank leaves)
    # broadcast correctly — a single caller-side reshape to one global
    # rank cannot serve them all. ``wsize(x, 1) == size(x)``.
    wsize: Callable[[State, Array], Array] = None


@dataclasses.dataclass(frozen=True)
class BatchWeights:
    """Weights for :attr:`Lattice.wsize` aligned to the LEADING (batch)
    axes of every state leaf.

    Plain-array weights broadcast right-aligned (against the universe
    axis — per-slot pricing). Per-batch pricing instead needs ``w`` to
    align left: each leaf right-pads ``w`` with singleton axes up to its
    own irreducible-mask rank (:func:`align_weights`). Doing this per
    leaf — not once at the caller with a single max-rank reshape — is
    what makes weighted accounting correct for mixed-rank lattices,
    where one product component's mask is [B, N, U] and another's is
    [B, N] (rank-0 universe): one global reshape either crashes or
    silently broadcasts ``w`` onto the wrong axis of the smaller leaf.
    """

    w: Any


def align_weights(w, mask):
    """Resolve a wsize weight operand against one leaf's irreducible
    mask: :class:`BatchWeights` are left-aligned (right-padded with
    singletons to the mask's rank), plain arrays pass through to
    ordinary right-aligned broadcasting."""
    if not isinstance(w, BatchWeights):
        return w
    wa = jnp.asarray(w.w)
    pad = jnp.ndim(mask) - wa.ndim
    if pad < 0:
        raise ValueError(
            f"BatchWeights rank {wa.ndim} exceeds the leaf mask rank "
            f"{jnp.ndim(mask)} — batch weights must index leading axes "
            f"of every leaf")
    return wa.reshape(wa.shape + (1,) * pad)


def leq_from_join(join, equal):
    """The canonical order  x ⊑ y ⇔ x ⊔ y = y  (paper §II)."""

    def leq(a, b):
        return equal(join(a, b), b)

    return leq


@dataclasses.dataclass(frozen=True)
class MapLattice:
    """Finite function  U ↪ V  from a static universe to a value lattice.

    This is the paper's ``U ↪ A`` construct (Appendix B, Table III): it
    preserves DCC and distributivity, so unique irredundant decompositions
    exist; they are the single-slot states (Birkhoff / Proposition 2).
    """

    universe: int
    value: ValueLattice
    name: str = "map"

    def _shape(self):
        return (self.universe,)

    def build(self) -> Lattice:
        v = self.value

        def bottom():
            return v.bottom(self._shape())

        def join(a, b):
            return v.join(a, b)

        def novel_mask(a, b):
            # slots whose irreducible in ⇓a is NOT ⊑ b
            return jnp.logical_and(
                jnp.logical_not(v.leq(a, b)),
                jnp.logical_not(v.is_bottom(a)),
            )

        def delta(a, b):
            # Δ(a,b): keep a's slot where its irreducible ⋢ b, else ⊥.
            keep = novel_mask(a, b)
            bot = v.bottom(())

            def sel(ai, boti):
                return jnp.where(keep, ai, boti)

            if v.arity == 1:
                return sel(a, bot)
            return tuple(sel(ai, boti) for ai, boti in zip(a, bot))

        def irreducible_mask(a):
            return jnp.logical_not(v.is_bottom(a))

        def size(a):
            return jnp.sum(irreducible_mask(a), axis=-1)

        def wsize(a, w):
            m = irreducible_mask(a)
            return jnp.sum(m * align_weights(w, m), axis=-1)

        def leq(a, b):
            return jnp.all(v.leq(a, b), axis=-1)

        def is_bottom(a):
            return jnp.all(v.is_bottom(a), axis=-1)

        # The value lattice declares which dense kernel matches its order
        # (one plane a point, or the megakernel's (t, v₁, …) planes).
        kind = v.kernel_kind

        return Lattice(
            name=self.name,
            bottom=bottom,
            join=join,
            leq=leq,
            delta=delta,
            size=size,
            is_bottom=is_bottom,
            irreducible_mask=irreducible_mask,
            novel_mask=novel_mask,
            kernel_kind=kind,
            wsize=wsize,
        )


def product(name: str, parts: Sequence[Lattice]) -> Lattice:
    """Cartesian product A × B (Table III: preserves DCC+distributivity).

    State is a tuple of sub-states; irreducibles are per-component (an
    irreducible of A×B is (j, ⊥) or (⊥, j) with j irreducible), so sizes add
    and Δ distributes componentwise.
    """
    parts = tuple(parts)

    def bottom():
        return tuple(p.bottom() for p in parts)

    def join(a, b):
        return tuple(p.join(x, y) for p, x, y in zip(parts, a, b))

    def leq(a, b):
        out = None
        for p, x, y in zip(parts, a, b):
            l = p.leq(x, y)
            out = l if out is None else jnp.logical_and(out, l)
        return out

    def delta(a, b):
        return tuple(p.delta(x, y) for p, x, y in zip(parts, a, b))

    def size(a):
        return sum(p.size(x) for p, x in zip(parts, a))

    def wsize(a, w):
        # Weight broadcasts per component — irreducibles of A × B live in
        # exactly one component, so weighted sizes add like sizes do.
        return sum(p.wsize(x, w) for p, x in zip(parts, a))

    def is_bottom(a):
        out = None
        for p, x in zip(parts, a):
            l = p.is_bottom(x)
            out = l if out is None else jnp.logical_and(out, l)
        return out

    def irreducible_mask(a):
        return tuple(p.irreducible_mask(x) for p, x in zip(parts, a))

    def novel_mask(a, b):
        return tuple(p.novel_mask(x, y) for p, x, y in zip(parts, a, b))

    return Lattice(
        name=name, bottom=bottom, join=join, leq=leq, delta=delta,
        size=size, is_bottom=is_bottom,
        irreducible_mask=irreducible_mask, novel_mask=novel_mask,
        wsize=wsize,
    )


# ---------------------------------------------------------------------------
# Explicit (materialized) decompositions — used by property tests and docs;
# production code uses the implicit masks above.
# ---------------------------------------------------------------------------

def decompose_dense(lat_map: MapLattice, x: State):
    """Materialize ⇓x as a stack of single-slot states, plus a validity mask.

    Returns (stack, mask) where ``stack`` has a new leading axis of length U;
    ``stack[k]`` is the irreducible for slot k (⊥ elsewhere) and ``mask[k]``
    says whether slot k is actually in ⇓x. Only for small universes (tests).
    """
    v = lat_map.value
    U = lat_map.universe
    eye = jnp.eye(U, dtype=jnp.bool_)

    def expand(arr):
        bot = v.bottom(())
        # arr: [..., U] -> [U, ..., U]
        return jnp.where(eye if arr.ndim == 1 else eye.reshape((U,) + (1,) * (arr.ndim - 1) + (U,)),
                         arr[None, ...], jnp.asarray(bot if not isinstance(bot, tuple) else 0, arr.dtype))

    if v.arity == 1:
        stack = expand(x)
        mask = jnp.logical_not(v.is_bottom(x))
        return stack, mask
    bots = v.bottom(())
    stacks = []
    for comp, bot in zip(x, bots):
        e = eye.reshape((U,) + (1,) * (comp.ndim - 1) + (U,))
        stacks.append(jnp.where(e, comp[None, ...], jnp.asarray(bot, comp.dtype)))
    mask = jnp.logical_not(v.is_bottom(x))
    return tuple(stacks), mask


def join_all(lat: Lattice, states, mask=None):
    """⊔ over a python sequence of states (tests/docs)."""
    acc = lat.bottom()
    for i, s in enumerate(states):
        if mask is not None and not bool(mask[i]):
            continue
        acc = lat.join(acc, s)
    return acc


def linear_sum(name: str, low: Lattice, high: Lattice,
               is_high) -> Lattice:
    """Linear sum A ⊕ B (paper Appendix B, Table III): every element of B
    is above every element of A. State = (tag, a_state, b_state) with tag
    0=low, 1=high; the inactive side is ⊥. Preserves DCC; distributivity
    per Table III.

    ``is_high``: not needed at runtime (the tag carries it) — kept for API
    symmetry with the paper's construct description.

    Batch-clean: tags are per-*point* scalars while side states carry
    universe axes, so every tag-driven select aligns the mask per leaf by
    the side's ⊥ rank (a bare ``jnp.where(tag_mask, side, ⊥)`` would
    right-align the node axis onto the universe axis for batched states —
    it only ever broadcast by coincidence when N == U).
    """

    def _tag_sel(mask, a, b, bot_ref):
        # mask [...] vs side leaves [..., *U]: grow one trailing singleton
        # per universe axis (the side lattice's ⊥ leaf rank).
        def sel(x, y, bl):
            c = mask.reshape(mask.shape + (1,) * jnp.ndim(bl))
            return jnp.where(c, x, y)

        return jax.tree.map(sel, a, b, bot_ref)

    def bottom():
        return (jnp.zeros((), jnp.int32), low.bottom(), high.bottom())

    def join(x, y):
        tx, ax, bx = x
        ty, ay, by = y
        tag = jnp.maximum(tx, ty)
        # joins within each side; when tags differ the high side wins and
        # the low side is discarded (absorbed below any high element)
        both_low = jnp.logical_and(tx == 0, ty == 0)
        a = low.join(ax, ay)
        b = high.join(bx, by)
        # low result only meaningful if both are low
        a_out = _tag_sel(both_low, a, jax.tree.map(jnp.zeros_like, a),
                         low.bottom())
        return (tag, a_out, b)

    def leq(x, y):
        tx, ax, bx = x
        ty, ay, by = y
        return jnp.where(
            tx < ty, True,
            jnp.where(tx > ty, False,
                      jnp.where(tx == 0, low.leq(ax, ay), high.leq(bx, by))))

    def delta(x, y):
        tx, ax, bx = x
        ty, ay, by = y
        # Optimal Δ: ⊥ whenever x ⊑ y (in particular any low x against a
        # high y, and high-vs-high with bx ⊑ by — emitting x's own side
        # there would be correct-but-not-minimal, breaking Δ-optimality).
        # The low side contributes only when BOTH are low (Δ within A);
        # a high x delegates to the high side's Δ, which against a low y
        # compares to ⊥_B and returns all of x's high irreducibles.
        da = low.delta(ax, ay)
        db = high.delta(bx, by)
        same_low = jnp.logical_and(tx == 0, ty == 0)
        a_out = _tag_sel(same_low, da, jax.tree.map(jnp.zeros_like, da),
                         low.bottom())
        tag = jnp.where(leq(x, y), jnp.zeros_like(tx), tx)
        return (tag, a_out, db)

    def size(x):
        tx, ax, bx = x
        return jnp.where(tx == 0, low.size(ax), high.size(bx))

    def wsize(x, w):
        tx, ax, bx = x
        return jnp.where(tx == 0, low.wsize(ax, w), high.wsize(bx, w))

    def is_bottom(x):
        tx, ax, bx = x
        return jnp.logical_and(tx == 0, low.is_bottom(ax))

    return Lattice(
        name=name, bottom=bottom, join=join, leq=leq, delta=delta,
        size=size, is_bottom=is_bottom,
        irreducible_mask=lambda x: (low.irreducible_mask(x[1]),
                                    high.irreducible_mask(x[2])),
        novel_mask=lambda a, b: (low.novel_mask(a[1], b[1]),
                                 high.novel_mask(a[2], b[2])),
        wsize=wsize,
    )
