"""Per-slot value lattices.

A *value lattice* defines the order over a single map entry (one "slot" of a
fixed universe). Map-like CRDT states are arrays of value-lattice points; the
join-irreducibles of the map state are exactly the single-slot states whose
slot value is non-bottom (see ``lattice.MapLattice``).

All operations are elementwise over arrays so they vectorize over both the
universe axis and any leading batch axes (e.g. the node axis of a simulated
cluster).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp

Array = Any


@dataclasses.dataclass(frozen=True)
class ValueLattice:
    """Elementwise lattice over array "points".

    A point is either a single array or a tuple of arrays (struct-of-arrays,
    e.g. lexicographic pairs). All callables are elementwise/broadcasting.
    """

    name: str
    # bottom(shape) -> point
    bottom: Callable[[tuple], Any]
    # join(a, b) -> point
    join: Callable[[Any, Any], Any]
    # leq(a, b) -> bool array   (pointwise a ⊑ b)
    leq: Callable[[Any, Any], Array]
    # is_bottom(a) -> bool array
    is_bottom: Callable[[Any], Array]
    # number of arrays making up a point (1 for scalar lattices)
    arity: int = 1
    # Dense Pallas kernel implementing this pointwise order ("max", "bitor",
    # "lex") or None — propagated to Lattice.kernel_kind for engine dispatch
    # (DESIGN.md §11/§17). Must only be set when the order really is the
    # kernel's (e.g. "max" ⇒ join is pointwise max / or on {0, 1}; "lex" ⇒
    # points are (t, v₁, …) tuples of planes under the lexicographic order).
    kernel_kind: str | None = None


def max_int(dtype=jnp.int32) -> ValueLattice:
    """Natural numbers under max — GCounter entries, GMap versions."""
    return ValueLattice(
        name=f"max_{jnp.dtype(dtype).name}",
        bottom=lambda shape: jnp.zeros(shape, dtype),
        join=jnp.maximum,
        leq=lambda a, b: a <= b,
        is_bottom=lambda a: a == 0,
        kernel_kind="max",
    )


def or_bool() -> ValueLattice:
    """Booleans under disjunction — GSet membership flags."""
    return ValueLattice(
        name="or_bool",
        bottom=lambda shape: jnp.zeros(shape, jnp.bool_),
        join=jnp.logical_or,
        leq=lambda a, b: jnp.logical_or(jnp.logical_not(a), b),
        is_bottom=jnp.logical_not,
        kernel_kind="max",        # or on {0, 1} ≡ pointwise max
    )


def lex_gt(a, b):
    """Pointwise a > b in the lexicographic order of two points (tuples
    of planes, the first deciding): the last plane's strict order, then
    each earlier plane's, from the back."""
    gt = a[-1] > b[-1]
    for ai, bi in zip(a[-2::-1], b[-2::-1]):
        gt = (ai > bi) | ((ai == bi) & gt)
    return gt


def lex(width: int = 1, ts_dtype=jnp.int32, val_dtype=jnp.int32,
        name: str | None = None) -> ValueLattice:
    """Lexicographic (version, value₁, …, value_width) — an LWW register
    whose value is ``width`` planes wide (a record of ``4·width`` bytes in
    int32 planes). The order is a chain: the newer version wins, a tie is
    broken by the values in plane order, so the join selects every plane
    of the larger point (paper Appendix B, Table III: a chain keeps the lex
    product distributive)."""
    if width < 1:
        raise ValueError(f"a lex point needs a value plane, not {width}")

    def bottom(shape):
        return (jnp.zeros(shape, ts_dtype),) + tuple(
            jnp.zeros(shape, val_dtype) for _ in range(width))

    def join(a, b):
        win = lex_gt(a, b)
        return tuple(jnp.where(win, ai, bi) for ai, bi in zip(a, b))

    def leq(a, b):
        return jnp.logical_not(lex_gt(a, b))

    def is_bottom(a):
        out = a[0] == 0
        for ai in a[1:]:
            out = out & (ai == 0)
        return out

    return ValueLattice(
        name=name or f"lex_{width + 1}", bottom=bottom, join=join, leq=leq,
        is_bottom=is_bottom, arity=width + 1, kernel_kind="lex",
    )


def lex_pair(ts_dtype=jnp.int32, val_dtype=jnp.int32) -> ValueLattice:
    """Lexicographic pair (version, value) — LWW registers / Cassandra-style
    counters (single-writer principle: the version is a chain, so the lex
    product stays distributive; see paper Appendix B, Table III)."""
    return lex(1, ts_dtype, val_dtype, name="lex_pair")
