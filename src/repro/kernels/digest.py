"""Digest kernels: blockwise summary reduction + masked block extraction.

The digest-driven sync mode (DESIGN.md §14) adds two hot per-round passes
over the [N, U] state:

* **digest reduction** — every ``block_elems``-wide universe block folds
  to three uint32 summary words ``[hash, count, agg]`` (layout defined by
  ``sync/digest.py``; the mixing constants and modular arithmetic are
  shared, so kernel and jnp reference agree bitwise);
* **masked extraction** — Δ(state, block_mask): per neighbor slot q, emit
  the state restricted to the blocks flagged by that slot's digest diff.
  The state tile is read ONCE and stays VMEM-resident while all P slot
  masks apply — the extraction analogue of ``round_recv``'s one-pass
  receive (a jnp composition would stream the state from HBM P times).

Layout: x is [M, N] (padded node rows × padded flattened universe); a
tile spans ``nb_t`` whole digest blocks, so digest blocks never span
tiles. Masks are int32 [P, M, NB] with NB = N // block_elems.

Sweep batching (DESIGN.md §13): ``batched=True`` prepends a config axis B
and the grid grows a leading batch dimension; every config's tiles run the
identical per-tile program, keeping sweep cells bit-identical to their
single-run equivalents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import interpret_default, pallas_call

# Node-axis sublanes of a digest tile. The universe extent of a tile is
# counted in digest blocks (``digest_tile``).
DIGEST_ROWS = 8


def digest_tile(u: int, be: int):
    """Digest tile ``(bm, nb_t)``: ``bm`` node rows by ``nb_t`` digest
    blocks of ``be`` elements. ``nb_t`` is a multiple of 8 (a sublane
    multiple for the [bm, nb_t, be] view of the state) and ``nb_t * be``
    is a multiple of 128 lanes; tiles span about 512 elements, and a short
    universe takes one tile of whole blocks."""
    unit = max(8, 128 // be)
    target = max(unit, 512 // be)
    nb = -(-u // be)
    return DIGEST_ROWS, min(target, -(-nb // unit) * unit)


def _pos_weights(be: int):
    # rank-3 iota: Mosaic rejects rank-1 iota on TPU; (1, 1, be)
    # broadcasts straight against the [bm, nblk, be] block view
    from repro.sync.digest import WMUL

    pos = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, be), 2)
    return (jnp.uint32(2) * pos + jnp.uint32(1)) * WMUL


def _wrap_sum(v):
    """Sum of uint32 words mod 2^32 over the last axis. Mosaic reduces no
    unsigned type; two's-complement int32 addition wraps identically."""
    i = jax.lax.bitcast_convert_type(v, jnp.int32)
    return jax.lax.bitcast_convert_type(
        jnp.sum(i, axis=-1, dtype=jnp.int32), jnp.uint32)


def _umax(v):
    """uint32 max over the last axis, as an int32 max of the words with
    the sign bit flipped (an order-preserving bijection)."""
    flip = jnp.uint32(0x80000000)
    i = jax.lax.bitcast_convert_type(v ^ flip, jnp.int32)
    return jax.lax.bitcast_convert_type(jnp.max(i, axis=-1),
                                        jnp.uint32) ^ flip


def _or_halves(v):
    """Or-reduce the trailing power-of-two axis by contiguous halves (the
    result equals ``sync.digest.or_fold``: or is commutative)."""
    while v.shape[-1] > 1:
        h = v.shape[-1] // 2
        v = v[..., :h] | v[..., h:]
    return v[..., 0]


def _digest_kernel(x_ref, h_ref, c_ref, a_ref, *, be: int, kind: str,
                   batched: bool):
    # The hash pipeline is IMPORTED from the canonical jnp digest, not
    # re-implemented: the engine bit-identity invariant rests on kernel
    # and reference agreeing word-for-word, so there is exactly one copy
    # of the mixing code. Deferred to trace time (like kernels/ref.py)
    # because a module-level import would be circular via
    # sync/__init__ -> engine -> kernels.ops -> kernels.digest.
    from repro.sync.digest import mix

    blk = x_ref[0] if batched else x_ref[...]      # [bm, nb_t, be] uint32
    h = _wrap_sum(mix((blk + jnp.uint32(1)) * _pos_weights(be)))
    cnt = jnp.sum((blk != 0).astype(jnp.int32), axis=-1,
                  dtype=jnp.int32).astype(jnp.uint32)
    agg = _or_halves(blk) if kind == "bitor" else _umax(blk)
    idx = (0, 0) if batched else (0,)
    h_ref[idx], c_ref[idx], a_ref[idx] = h, cnt, agg


@functools.partial(
    jax.jit, static_argnames=("be", "kind", "block", "interpret", "batched"))
def digest_blocks_2d(x, *, be: int, kind: str = "max", block,
                     interpret: bool | None = None, batched: bool = False):
    """x: [(B,) M, NB * be] uint32, tile-aligned for ``block`` =
    ``(bm, nb_t)`` (``digest_tile``). Returns (hash, count, agg) each
    [(B,) M, NB] uint32.

    The kernel reads the state as a [M, NB, be] view, so each digest
    block lies along the lane axis of its own row (Mosaic cannot split
    the lane axis in-kernel), and writes each universe tile's summaries to
    its own [M, nb_t] plane of a [GJ, M, nb_t] output, whose block spans
    the full trailing axis; the wrapper interleaves the planes back."""
    interpret = interpret_default() if interpret is None else interpret
    assert x.dtype == jnp.uint32
    bm, nb_t = block
    lead = x.shape[:1] if batched else ()
    m, n = x.shape[-2:]
    nb = n // be
    assert n % be == 0 and m % bm == 0 and nb % nb_t == 0
    gi, gj = m // bm, nb // nb_t
    x3 = x.reshape(lead + (m, nb, be))
    if batched:
        grid = lead + (gi, gj)
        x_spec = pl.BlockSpec((1, bm, nb_t, be), lambda b, i, j: (b, i, j, 0))
        o_spec = pl.BlockSpec((1, 1, bm, nb_t), lambda b, i, j: (b, j, i, 0))
    else:
        grid = (gi, gj)
        x_spec = pl.BlockSpec((bm, nb_t, be), lambda i, j: (i, j, 0))
        o_spec = pl.BlockSpec((1, bm, nb_t), lambda i, j: (j, i, 0))
    o_shape = jax.ShapeDtypeStruct(lead + (gj, m, nb_t), jnp.uint32)
    outs = pallas_call(
        functools.partial(_digest_kernel, be=be, kind=kind, batched=batched),
        grid=grid,
        in_specs=[x_spec],
        out_specs=[o_spec] * 3,
        out_shape=[o_shape] * 3,
        interpret=interpret,
    )(x3)
    # [(B,) GJ, M, nb_t] -> [(B,) M, GJ * nb_t]
    return tuple(jnp.moveaxis(o, -3, -2).reshape(lead + (m, nb))
                 for o in outs)


def _extract_kernel(x_ref, m_ref, o_ref, *, p: int, be: int, batched: bool):
    v = x_ref[0] if batched else x_ref[...]              # [bm, bn], resident
    bm, bn = v.shape
    zero = jnp.zeros((), v.dtype)
    for q in range(p):
        mq = m_ref[q, 0, 0] if batched else m_ref[q, 0]  # [bm, bn // be]
        full = jnp.broadcast_to(mq[:, :, None],
                                (bm, bn // be, be)).reshape(bm, bn)
        out = jnp.where(full != 0, v, zero)
        if batched:
            o_ref[q, 0] = out
        else:
            o_ref[q] = out


@functools.partial(
    jax.jit, static_argnames=("be", "block", "interpret", "batched"))
def masked_extract_2d(x, masks, *, be: int, block,
                      interpret: bool | None = None, batched: bool = False):
    """x: [(B,) M, N] tile-aligned for ``block`` = ``(bm, nb_t)``
    (``digest_tile``), masks: int32 [P, (B,) M, N // be]. Returns
    [P, (B,) M, N]: slot q's state restricted to its masked blocks (⊥ = 0
    elsewhere), with the x tile read once for all P slots.

    The masks reach the kernel as [P, (B,) GJ, M, nb_t] planes, one per
    universe tile, so every mask block spans its full trailing axis."""
    interpret = interpret_default() if interpret is None else interpret
    bm, nb_t = block
    bn = nb_t * be
    lead = x.shape[:1] if batched else ()
    m, n = x.shape[-2:]
    p = masks.shape[0]
    assert masks.shape == (p,) + lead + (m, n // be)
    assert m % bm == 0 and n % bn == 0
    gi, gj = m // bm, n // bn
    mk = masks.reshape((p,) + lead + (m, gj, nb_t))
    mk = jnp.moveaxis(mk, -2, -3)                  # [P, (B,) GJ, M, nb_t]
    if batched:
        grid = lead + (gi, gj)
        x_spec = pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j))
        m_spec = pl.BlockSpec((p, 1, 1, bm, nb_t),
                              lambda b, i, j: (0, b, j, i, 0))
        o_spec = pl.BlockSpec((p, 1, bm, bn), lambda b, i, j: (0, b, i, j))
    else:
        grid = (gi, gj)
        x_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
        m_spec = pl.BlockSpec((p, 1, bm, nb_t), lambda i, j: (0, j, i, 0))
        o_spec = pl.BlockSpec((p, bm, bn), lambda i, j: (0, i, j))
    return pallas_call(
        functools.partial(_extract_kernel, p=p, be=be, batched=batched),
        grid=grid,
        in_specs=[x_spec, m_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((p,) + x.shape, x.dtype),
        interpret=interpret,
    )(x, mk)
