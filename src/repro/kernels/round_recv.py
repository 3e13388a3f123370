"""Fused sync-round receive: one HBM pass for Algorithm 2's lines 14-17.

The reference engine's receive phase walks the P neighbor slots in Python,
issuing 3+ separate jnp passes (join, Δ-extract, size/leq) over the [N, U]
state per slot — one synchronous round streams the universe from HBM ~O(P)
times. This kernel executes the *whole* sequential receive in a single tiled
pass (DESIGN.md §11): the grid covers (node, universe) tiles, the state tile
stays resident in VMEM, and the P gathered δ-groups are folded in slot order

    for q in 0..P-1:                     # Alg 2 slot-order semantics
        novel_q   = ⇓d_q ⋢ x             # vs the RUNNING state
        stored_q  = Δ(d_q, x)            # RR extraction
        cnt_q     = |⇓stored_q|          # per-node novel count
        dsz_q     = |⇓d_q|               # per-node received size
        x         = x ⊔ d_q

so every engine decision that the reference loop makes from global
reductions (inflation check ¬(d ⊑ x) ⇔ cnt > 0, ⊥-check Δ = ⊥ ⇔ cnt = 0)
is recoverable from the emitted per-(node, slot) counts — no second pass.

Kinds: ``max`` (ℕ-max / bool-or value lattices) and ``bitor`` (bit-packed
sets; novelty = d & ~x, counts via popcount).

Layout: d is [P, M, N] (slot-major so one (m, n) tile of all P slots is
co-resident in VMEM: P ≤ 8 slots × 8×512 int32 = ≤ 128 KiB per stack), x is
[M, N]; M = padded node axis, N = padded (flattened) universe axis. Counts
are emitted per grid block and reduced by the wrapper, mirroring
``delta_extract_2d``.

Sweep batching (DESIGN.md §13): ``batched=True`` prepends a config axis B
(d [P, B, M, N], x [B, M, N]) and the grid grows a leading batch dimension
(B, gi, gj) — each config's (m, n) tiles run the *identical* per-tile
program the unbatched grid runs, so every sweep cell is bit-identical to
its single-run equivalent.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import grid_for, interpret_default, pallas_call

# Node-axis sublanes × universe-axis lanes. The node axis of real
# deployments is small next to the universe axis, so the default tile is
# short and wide.
ROUND_BLOCK = (8, 512)


def _popcount_rows(a):
    # dtype pinned: under x64 (simulate's wide-metrics context) jnp.sum
    # would promote to int64 and mismatch the int32 count refs.
    return jnp.sum(jax.lax.population_count(a).astype(jnp.int32), axis=-1,
                   dtype=jnp.int32)


def _round_recv_kernel(d_ref, x_ref, a_ref, *o_refs, p: int, kind: str,
                       emit_stored: bool, emit_cov: bool, batched: bool):
    o_refs = list(o_refs)
    xo_ref = o_refs.pop(0)
    s_ref = o_refs.pop(0) if emit_stored else None
    cov_ref = o_refs.pop(0) if emit_cov else None
    cnt_ref, dsz_ref = o_refs
    # Batched blocks carry a singleton config dim (the batch grid axis maps
    # each config to its own block) — index it away so the fold body is the
    # same program either way.
    x = x_ref[0] if batched else x_ref[...]               # [bm, bn], VMEM
    act = a_ref[0] if batched else a_ref[...]             # [bm, p] active
    # Per-element delivery tally (provenance, DESIGN.md §19): how many
    # active slots shipped each universe slot this round. Word-granular
    # for bit-packed states (popcount of delivered bits per word), same
    # granularity as the lattice's irreducible_mask.
    cov = jnp.zeros(x.shape, jnp.int32) if emit_cov else None
    for q in range(p):
        # Active-slot mask (topology padding ∧ fault delivery, DESIGN.md
        # §12): a suppressed slot is ⊥ — contributes nothing to x, counts,
        # or stored extractions. Masking here (in VMEM) replaces a whole
        # jnp.where pass over the [N, P, U] inbox in HBM.
        dq = d_ref[q, 0] if batched else d_ref[q]
        d = jnp.where(act[:, q][:, None] != 0, dq,
                      jnp.zeros((), d_ref.dtype))
        if kind == "max":
            novel = d > x                  # irreducible of d strictly above x
            s = jnp.where(novel, d, jnp.zeros_like(d))
            cnt = jnp.sum(novel, axis=-1, dtype=jnp.int32)
            dsz = jnp.sum(d != 0, axis=-1, dtype=jnp.int32)
            x = jnp.maximum(x, d)
        elif kind == "bitor":
            s = jnp.bitwise_and(d, jnp.bitwise_not(x))
            cnt = _popcount_rows(s)
            dsz = _popcount_rows(d)
            x = jnp.bitwise_or(x, d)
        else:
            raise ValueError(kind)
        if emit_stored:
            if batched:
                s_ref[q, 0] = s
            else:
                s_ref[q] = s
        cnt_idx = (0, 0, 0, slice(None), q) if batched \
            else (0, 0, slice(None), q)
        cnt_ref[cnt_idx] = cnt
        dsz_ref[cnt_idx] = dsz
        if emit_cov:
            if kind == "max":
                cov = cov + (d != 0).astype(jnp.int32)
            else:
                cov = cov + jax.lax.population_count(d).astype(jnp.int32)
    if batched:
        xo_ref[0] = x
        if emit_cov:
            cov_ref[0] = cov
    else:
        xo_ref[...] = x
        if emit_cov:
            cov_ref[...] = cov


@functools.partial(
    jax.jit,
    static_argnames=("kind", "block", "interpret", "emit_stored", "emit_cov",
                     "batched"))
def round_recv_2d(d, x, active=None, *, kind: str = "max", block=ROUND_BLOCK,
                  interpret: bool | None = None, emit_stored: bool = True,
                  emit_cov: bool = False, batched: bool = False):
    """d: [P, (B,) M, N] slot-major gathered δ-groups, x: [(B,) M, N],
    tile-aligned; ``batched`` declares the extra leading config axis B
    (DESIGN.md §13), which becomes the leading batch grid dimension.

    ``active``: optional int32 [(B,) M, P] per-(node, slot) mask — 0
    suppresses the slot entirely (topology padding or an injected fault,
    DESIGN.md §12); None means all slots active.

    Returns ``(x', stored, cov, cnt, dsz)`` with ``stored`` [P, (B,) M, N]
    the slot-order RR extractions (None when ``emit_stored=False``),
    ``cov`` [(B,) M, N] int32 the per-element delivery tally (None unless
    ``emit_cov``: per universe slot, how many active slots delivered it —
    popcounted per word for kind "bitor"), and ``cnt``/``dsz``
    [(B,) gi, gj, bm, P] per-block per-node counts (sum the gj axis to get
    the [(B,) M, P] totals). Tiles own disjoint elements, so ``cov`` needs
    no cross-block reduction.
    """
    interpret = interpret_default() if interpret is None else interpret
    if batched:
        p, bcfg, m, n = d.shape
        assert x.shape == (bcfg, m, n) and d.dtype == x.dtype
    else:
        p, m, n = d.shape
        assert x.shape == (m, n) and d.dtype == x.dtype
    if active is None:
        active = jnp.ones(x.shape[:-1] + (p,), jnp.int32)
    assert active.shape == x.shape[:-1] + (p,)
    active = active.astype(jnp.int32)
    bm, bn = block
    tiles = grid_for((m, n), block)
    if batched:
        grid = (bcfg,) + tiles
        d_spec = pl.BlockSpec((p, 1, bm, bn), lambda b, i, j: (0, b, i, j))
        x_spec = pl.BlockSpec((1, bm, bn), lambda b, i, j: (b, i, j))
        a_spec = pl.BlockSpec((1, bm, p), lambda b, i, j: (b, i, 0))
        cnt_spec = pl.BlockSpec((1, 1, 1, bm, p),
                                lambda b, i, j: (b, i, j, 0, 0))
        cnt_shape = jax.ShapeDtypeStruct((bcfg,) + tiles + (bm, p), jnp.int32)
    else:
        grid = tiles
        d_spec = pl.BlockSpec((p, bm, bn), lambda i, j: (0, i, j))
        x_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
        a_spec = pl.BlockSpec((bm, p), lambda i, j: (i, 0))
        cnt_spec = pl.BlockSpec((1, 1, bm, p), lambda i, j: (i, j, 0, 0))
        cnt_shape = jax.ShapeDtypeStruct(tiles + (bm, p), jnp.int32)
    out_specs = [x_spec] + ([d_spec] if emit_stored else []) \
        + ([x_spec] if emit_cov else []) + [cnt_spec, cnt_spec]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)] \
        + ([jax.ShapeDtypeStruct(d.shape, d.dtype)] if emit_stored else []) \
        + ([jax.ShapeDtypeStruct(x.shape, jnp.int32)] if emit_cov else []) \
        + [cnt_shape, cnt_shape]
    outs = pallas_call(
        functools.partial(_round_recv_kernel, p=p, kind=kind,
                          emit_stored=emit_stored, emit_cov=emit_cov,
                          batched=batched),
        grid=grid,
        in_specs=[d_spec, x_spec, a_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(d, x, active)
    outs = list(outs)
    xo = outs.pop(0)
    s = outs.pop(0) if emit_stored else None
    cov = outs.pop(0) if emit_cov else None
    cnt, dsz = outs
    return xo, s, cov, cnt, dsz
