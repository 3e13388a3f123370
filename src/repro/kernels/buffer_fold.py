"""Leave-one-out buffer fold: per-neighbor BP sends (paper §IV, Alg 2 l.11).

Given the origin-indexed δ-buffer B[K, M, N] (K = P neighbors + 1 self slot),
produce all P per-neighbor sends

    send[j] = ⊔ { B[o] | o ≠ j },   j = 0..P-1

in ONE pass over the buffer using prefix/suffix joins inside the tile
(O(K·tile) work, vs the naive O(K²·tile) refold — DESIGN.md §9). The whole
K-deep stack of one (m, n) tile sits in VMEM simultaneously: K ≤ 9 slots ×
256 KiB default tile = ≤ 2.25 MiB.

Kind ``max`` covers ℕ-max and 0/1-or lattices; ``bitor`` covers packed sets.

Sweep batching (DESIGN.md §13): ``batched=True`` prepends a config axis B
(buf [K, B, M, N]) and the grid grows a leading batch dimension
(B, gi, gj); each config's tiles run the identical fold, so sweep cells
stay bit-identical to their single-run equivalents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import grid_for, interpret_default, pallas_call

FOLD_BLOCK = (256, 256)


def _fold_kernel(b_ref, o_ref, *, k: int, kind: str, batched: bool):
    op = jnp.maximum if kind == "max" else jnp.bitwise_or
    # Batched blocks carry a singleton config dim — index it away so the
    # prefix/suffix fold is the same program either way.
    slots = [b_ref[i, 0] if batched else b_ref[i] for i in range(k)]
    zero = jnp.zeros_like(slots[0])
    prefix = [zero] * k
    suffix = [zero] * k
    acc = zero
    for i in range(k):
        prefix[i] = acc
        acc = op(acc, slots[i])
    acc = zero
    for i in range(k - 1, -1, -1):
        suffix[i] = acc
        acc = op(acc, slots[i])
    for j in range(k - 1):        # sends only for the P neighbor slots
        if batched:
            o_ref[j, 0] = op(prefix[j], suffix[j])
        else:
            o_ref[j] = op(prefix[j], suffix[j])


@functools.partial(
    jax.jit, static_argnames=("kind", "block", "interpret", "batched"))
def buffer_fold_2d(buf, *, kind: str = "max", block=FOLD_BLOCK,
                   interpret: bool | None = None, batched: bool = False):
    """buf: [K, (B,) M, N] tile-aligned -> sends [K-1, (B,) M, N];
    ``batched`` declares the extra leading config axis B, which becomes
    the leading batch grid dimension."""
    interpret = interpret_default() if interpret is None else interpret
    if batched:
        k, bcfg, m, n = buf.shape
    else:
        k, m, n = buf.shape
    bm, bn = block
    tiles = grid_for((m, n), block)
    if batched:
        grid = (bcfg,) + tiles
        in_spec = pl.BlockSpec((k, 1, bm, bn), lambda b, i, j: (0, b, i, j))
        out_spec = pl.BlockSpec((k - 1, 1, bm, bn),
                                lambda b, i, j: (0, b, i, j))
        out_shape = jax.ShapeDtypeStruct((k - 1, bcfg, m, n), buf.dtype)
    else:
        grid = tiles
        in_spec = pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j))
        out_spec = pl.BlockSpec((k - 1, bm, bn), lambda i, j: (0, i, j))
        out_shape = jax.ShapeDtypeStruct((k - 1, m, n), buf.dtype)
    return pallas_call(
        functools.partial(_fold_kernel, k=k, kind=kind, batched=batched),
        grid=grid,
        in_specs=[in_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(buf)
