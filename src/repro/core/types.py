"""Concrete state-based CRDTs (paper §II-A, Appendix B).

Each type bundles a `Lattice` with its mutators m and optimal δ-mutators
mᵟ(x) = Δ(m(x), x). States are plain jnp arrays (or tuples thereof), so they
nest into pytrees, `lax.scan` carries, and pjit shardings without wrappers.

Dense-universe adaptation (DESIGN.md §3): element/key/replica identifiers are
static integer indices into a fixed universe.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import value_lattices as vl
from repro.core.lattice import Lattice, MapLattice, align_weights, product


# ---------------------------------------------------------------------------
# GCounter  (Figure 2a)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GCounter:
    """Grow-only counter: I ↪ ℕ under pointwise max."""

    num_replicas: int

    @property
    def lattice(self) -> Lattice:
        return MapLattice(self.num_replicas, vl.max_int(), "gcounter").build()

    def inc(self, p, i):
        """m: p{i ↦ p(i)+1}"""
        return p.at[i].add(1)

    def inc_delta(self, p, i):
        """mᵟ: {i ↦ p(i)+1} — a single irreducible (optimal)."""
        d = jnp.zeros_like(p)
        return d.at[i].set(p[i] + 1)

    def value(self, p):
        return jnp.sum(p, axis=-1)


# ---------------------------------------------------------------------------
# PNCounter  (product of two GCounters; Appendix B: A × B)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PNCounter:
    num_replicas: int

    @property
    def lattice(self) -> Lattice:
        g = MapLattice(self.num_replicas, vl.max_int(), "g").build()
        return product("pncounter", (g, g))

    def inc(self, s, i):
        p, n = s
        return (p.at[i].add(1), n)

    def dec(self, s, i):
        p, n = s
        return (p, n.at[i].add(1))

    def inc_delta(self, s, i):
        p, n = s
        d = jnp.zeros_like(p).at[i].set(p[i] + 1)
        return (d, jnp.zeros_like(n))

    def dec_delta(self, s, i):
        p, n = s
        d = jnp.zeros_like(n).at[i].set(n[i] + 1)
        return (jnp.zeros_like(p), d)

    def value(self, s):
        p, n = s
        return jnp.sum(p, axis=-1) - jnp.sum(n, axis=-1)


# ---------------------------------------------------------------------------
# GSet  (Figure 2b)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GSet:
    """Grow-only set over a static universe, P(E) under union."""

    universe: int

    @property
    def lattice(self) -> Lattice:
        return MapLattice(self.universe, vl.or_bool(), "gset").build()

    def add(self, s, e):
        return s.at[e].set(True)

    def add_delta(self, s, e):
        """mᵟ: {e} if e ∉ s else ⊥ (the paper's *optimal* addᵟ)."""
        d = jnp.zeros_like(s)
        return d.at[e].set(jnp.logical_not(s[e]))

    def add_mask(self, s, mask):
        return jnp.logical_or(s, mask)

    def add_mask_delta(self, s, mask):
        return jnp.logical_and(mask, jnp.logical_not(s))

    def value(self, s):
        return s


# ---------------------------------------------------------------------------
# BitGSet: bit-packed grow-only set (beyond-paper wire/memory format,
# DESIGN.md §9). The universe is packed 32 elements per uint32 word, so the
# state is 8× denser than the boolean GSet and joins/Δ run on whole words.
# Irreducibles are single *bits*; `size` counts them via popcount, while the
# pointwise mask views resolve at word granularity (each word is the join of
# its bit irreducibles — use `kernels.ops.unpack_bits` for bit resolution).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BitGSet:
    """Grow-only set over a static universe, bit-packed into uint32 words."""

    universe: int

    @property
    def num_words(self) -> int:
        return -(-self.universe // 32)

    @property
    def lattice(self) -> Lattice:
        w = self.num_words

        def bottom():
            return jnp.zeros((w,), jnp.uint32)

        def join(a, b):
            return jnp.bitwise_or(a, b)

        def delta(a, b):
            # Δ(a,b): a's bits absent from b — exact and optimal (each bit
            # is one irreducible).
            return jnp.bitwise_and(a, jnp.bitwise_not(b))

        def size(a):
            return jnp.sum(jax.lax.population_count(a).astype(jnp.int32),
                           axis=-1)

        def wsize(a, wt):
            # per-word weights (bits of one word share a weight)
            pc = jax.lax.population_count(a).astype(jnp.int32)
            return jnp.sum(pc * align_weights(wt, pc), axis=-1)

        def leq(a, b):
            return jnp.all(delta(a, b) == 0, axis=-1)

        def is_bottom(a):
            return jnp.all(a == 0, axis=-1)

        def irreducible_mask(a):
            return a != 0          # word-level view

        def novel_mask(a, b):
            return delta(a, b) != 0

        return Lattice(
            name="bitgset",
            bottom=bottom,
            join=join,
            leq=leq,
            delta=delta,
            size=size,
            is_bottom=is_bottom,
            irreducible_mask=irreducible_mask,
            novel_mask=novel_mask,
            kernel_kind="bitor",
            wsize=wsize,
        )

    def add_mask(self, s, mask_words):
        """m: union in a packed word mask."""
        return jnp.bitwise_or(s, mask_words)

    def add_mask_delta(self, s, mask_words):
        """mᵟ: only the bits not already present (optimal addᵟ)."""
        return jnp.bitwise_and(mask_words, jnp.bitwise_not(s))


# ---------------------------------------------------------------------------
# GMap (K% benchmark, Table I): keys ↪ max-versioned values.
#
# The paper's GMap micro-benchmark "changes the value of K/N% of keys" per
# node per tick; each change inflates the per-key value lattice. We model the
# per-key value as a version counter under max (a chain), which is exactly
# what makes GCounter "a particular case of GMap with K=100".
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GMap:
    num_keys: int

    @property
    def lattice(self) -> Lattice:
        return MapLattice(self.num_keys, vl.max_int(), "gmap").build()

    def bump(self, m, key_mask):
        """m: inflate the value of every key in ``key_mask``."""
        return m + key_mask.astype(m.dtype)

    def bump_delta(self, m, key_mask):
        """mᵟ: only the updated entries, at their new versions (optimal)."""
        return jnp.where(key_mask, m + 1, jnp.zeros_like(m))


# ---------------------------------------------------------------------------
# LWWMap: keys ↪ lexicographic (timestamp, value) — Retwis walls/timelines.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LWWMap:
    """``width`` > 1: each key holds a record of ``width`` int32 planes,
    the state (t, v₁, …, v_width) under the lexicographic order; ``put``
    then takes ``width`` values."""

    num_keys: int
    width: int = 1

    @property
    def lattice(self) -> Lattice:
        value = vl.lex_pair() if self.width == 1 else vl.lex(self.width)
        return MapLattice(self.num_keys, value, "lwwmap").build()

    def _vals(self, val):
        return (val,) if self.width == 1 else tuple(val)

    def put(self, s, key, ts, val):
        return tuple(c.at[key].set(w)
                     for c, w in zip(s, (ts,) + self._vals(val)))

    def put_delta(self, s, key, ts, val):
        return tuple(jnp.zeros_like(c).at[key].set(w)
                     for c, w in zip(s, (ts,) + self._vals(val)))


# ---------------------------------------------------------------------------
# LexCounter: I ↪ (ℕ ⊠ ℕ) — Cassandra-style counter (Appendix B: the
# single-writer principle keeps the lex product distributive because the
# first component is a chain and only the owner writes its own entry).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LexCounter:
    num_replicas: int

    @property
    def lattice(self) -> Lattice:
        return MapLattice(self.num_replicas, vl.lex_pair(), "lexcounter").build()

    def set_value(self, s, i, val):
        """Owner i sets its component to an arbitrary value, bumping the
        version (the paper's 'inflate or change arbitrarily' usage)."""
        t, v = s
        return (t.at[i].add(1), v.at[i].set(val))

    def set_value_delta(self, s, i, val):
        t, v = s
        dt = jnp.zeros_like(t).at[i].set(t[i] + 1)
        dv = jnp.zeros_like(v).at[i].set(val)
        return (dt, dv)

    def value(self, s):
        _, v = s
        return jnp.sum(v, axis=-1)
