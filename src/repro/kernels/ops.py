"""Public jit'd wrappers around the CRDT Pallas kernels.

Handle arbitrary state shapes by flattening + ⊥-padding to tile multiples
(⊥ = 0 for every supported value lattice, so padding is inert), dispatch to
the tiled kernels, and unpad. ``interpret`` defaults to True off-TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import common, ref
from repro.kernels.buffer_fold import FOLD_BLOCK, buffer_fold_2d
from repro.kernels.common import (
    BOOL_VIEW,
    DEFAULT_BLOCK,
    interpret_default,
    pad_to_2d,
    unpad_from_2d,
)
from repro.kernels.common import LANE, SUBLANE
from repro.kernels.delta_extract import delta_extract_2d
from repro.kernels.digest import digest_blocks_2d, digest_tile, masked_extract_2d
from repro.kernels.join import join_2d
from repro.kernels.round_recv import ROUND_BLOCK, round_recv_2d
from repro.kernels.round_step import planes, point, round_step_2d, tile_bytes

# The blocks of one megakernel grid step that a wide lex point's default
# tile may take (double-buffered; the round's values need as much again).
VMEM_BUDGET = 24 << 20


def _tiled_2d(kernel_2d, operands, *, block, interpret, **kw):
    """Shared elementwise-kernel prolog: flatten/⊥-pad every operand to the
    same [M, N] tiling, invoke the 2D entry point, unpad array outputs.

    Scalar outputs (counts) pass through untouched; array outputs are
    unpadded back to the first operand's shape. Deduplicates the prologs of
    ``join``/``delta_extract`` (DESIGN.md §17).
    """
    interpret = interpret_default() if interpret is None else interpret
    shape = n = None
    padded = []
    for a in operands:
        a2, s, ln = pad_to_2d(a, block)
        if shape is None:
            shape, n = s, ln
        padded.append(a2)
    outs = kernel_2d(*padded, block=block, interpret=interpret, **kw)
    one = not isinstance(outs, (tuple, list))
    outs = (outs,) if one else outs
    unp = [unpad_from_2d(o, shape, n) if getattr(o, "ndim", 0) == 2 else o
           for o in outs]
    return unp[0] if one else tuple(unp)


def join(a, b, *, kind: str = "max", block=DEFAULT_BLOCK, interpret=None):
    """Lattice join a ⊔ b over arbitrary-shaped dense states."""
    return _tiled_2d(join_2d, (a, b), block=block, interpret=interpret,
                     kind=kind)


def delta_extract(d, x, *, kind: str = "max", block=DEFAULT_BLOCK, interpret=None):
    """Fused RR step: returns (Δ(d,x), x ⊔ d, |⇓Δ|)."""
    return _tiled_2d(delta_extract_2d, (d, x), block=block,
                     interpret=interpret, kind=kind)


def buffer_fold(buf, *, kind: str = "max", block=FOLD_BLOCK, interpret=None,
                batched: bool = False, layout: str = "grid"):
    """Per-neighbor BP sends from an origin-indexed buffer [K, ...U] ->
    [K-1, ...U] leave-one-out joins.

    ``batched=True`` treats axis 1 as a sweep config axis (buf
    [K, B, ...U], DESIGN.md §13): each config is tiled separately under a
    leading batch grid dimension, so per-config results are bit-identical
    to folding that config alone. ``layout="rows"`` (store engine,
    DESIGN.md §15) instead folds the config axis into the flattened tile
    row space — the fold is elementwise across slots, so results are
    bit-identical either way, but B small objects become one large launch
    instead of B grid steps.
    """
    interpret = interpret_default() if interpret is None else interpret
    k = buf.shape[0]
    bm, bn = block
    cols = bn
    if batched and layout == "rows":
        batched = False                 # flat path tiles [K, B·N·U] rows
    if batched:
        bcfg = buf.shape[1]
        flat = buf.reshape(k, bcfg, -1)
        n = flat.shape[2]
        rows = -(-n // cols)
        rows_pad = -(-rows // bm) * bm
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, rows_pad * cols - n)))
        out = buffer_fold_2d(
            flat.reshape(k, bcfg, rows_pad, cols), kind=kind, block=block,
            interpret=interpret, batched=True)
        return out.reshape(k - 1, bcfg, -1)[:, :, :n] \
            .reshape((k - 1,) + buf.shape[1:])
    flat = buf.reshape(k, -1)
    n = flat.shape[1]
    rows = -(-n // cols)
    rows_pad = -(-rows // bm) * bm
    flat = jnp.pad(flat, ((0, 0), (0, rows_pad * cols - n)))
    out = buffer_fold_2d(
        flat.reshape(k, rows_pad, cols), kind=kind, block=block, interpret=interpret
    )
    return out.reshape(k - 1, -1)[:, :n].reshape((k - 1,) + buf.shape[1:])


def round_recv(d_stack, x, *, kind: str = "max", block=None, interpret=None,
               emit_stored: bool = True, emit_cov: bool = False, active=None,
               layout: str = "grid"):
    """Fused one-pass sync-round receive (DESIGN.md §11).

    ``d_stack``: [P, B, U] gathered per-slot δ-groups, ``x``: [B, U]
    states. ``active``: optional bool/int [B, P] per-(node, slot) mask —
    0/False suppresses the slot inside the kernel (topology padding or an
    injected fault, DESIGN.md §12); with ``active=None`` the caller must
    pre-mask invalid slots to ⊥. Returns ``(x', stored, cov, cnt, dsz)``
    where ``x'`` is the state after joining all P slots in order,
    ``stored`` [P, B, U] holds the slot-order RR extractions
    Δ(d_q, x_running) (None when ``emit_stored=False``), ``cov`` [B, U]
    int32 the per-element delivery tally (None unless ``emit_cov``; how
    many active slots delivered each universe slot — popcounted per word
    for kind "bitor"; provenance, DESIGN.md §19), and ``cnt``/``dsz``
    [B, P] count each slot's novel / received irreducibles per node.

    Sweep batching (DESIGN.md §13): a rank-3 ``x`` ([C, B, U] with a
    leading config axis, ``d_stack`` [P, C, B, U], ``active`` [C, B, P])
    dispatches to the kernel's leading batch grid dimension; counts come
    back [C, B, P]. Per-cell results are bit-identical to unbatched calls.

    ``layout="rows"`` (store engine, DESIGN.md §15) flattens a rank-3
    batch into the tile row axis instead — ([C·B, U] rows with a taller
    tile), the right shape for millions of small objects: one launch with
    large tiles instead of C tiny grid steps. Every per-row computation
    is independent, so both layouts are bit-identical.

    Boolean states are viewed as ``common.BOOL_VIEW`` {0, 1} words for the
    kernel (max ≡ or) and cast back — bit-identical.
    """
    interpret = interpret_default() if interpret is None else interpret
    if x.ndim == 3 and layout == "rows":
        p, c, b, u = d_stack.shape
        rows = c * b
        if block is None:
            # Tall tiles amortize grid steps over the flattened
            # (object, node) rows; short universes stay lane-aligned.
            bm = 128 if rows >= 128 else ROUND_BLOCK[0]
            block = (bm, min(ROUND_BLOCK[1], -(-u // LANE) * LANE))
        xo, s, cov, cnt, dsz = round_recv(
            d_stack.reshape(p, rows, u), x.reshape(rows, u), kind=kind,
            block=block, interpret=interpret, emit_stored=emit_stored,
            emit_cov=emit_cov,
            active=None if active is None else active.reshape(rows, p))
        xo = xo.reshape(c, b, u)
        if s is not None:
            s = s.reshape(p, c, b, u)
        if cov is not None:
            cov = cov.reshape(c, b, u)
        return xo, s, cov, cnt.reshape(c, b, p), dsz.reshape(c, b, p)
    batched = x.ndim == 3
    if batched:
        p, c, b, u = d_stack.shape
        assert x.shape == (c, b, u)
    else:
        p, b, u = d_stack.shape
        assert x.shape == (b, u)
    orig_dtype = x.dtype
    if orig_dtype == jnp.bool_:
        d_stack = d_stack.astype(BOOL_VIEW)
        x = x.astype(BOOL_VIEW)
    if block is None:
        # Short universes take one lane-aligned tile instead of the full
        # default width so interpret-mode tests don't pad 10×.
        block = (ROUND_BLOCK[0], min(ROUND_BLOCK[1], -(-u // LANE) * LANE))
    bm, bn = block
    m_pad = -(-b // bm) * bm
    n_pad = -(-u // bn) * bn
    lead = ((0, 0),) * (2 if batched else 1)
    d2 = jnp.pad(d_stack, lead + ((0, m_pad - b), (0, n_pad - u)))
    x2 = jnp.pad(x, lead[:-1] + ((0, m_pad - b), (0, n_pad - u)))
    if active is None:
        a2 = None
    else:
        assert active.shape == x.shape[:-1] + (p,)
        a2 = jnp.pad(active.astype(jnp.int32),
                     lead[:-1] + ((0, m_pad - b), (0, 0)))
    xo, s, cov, cnt, dsz = round_recv_2d(
        d2, x2, a2, kind=kind, block=block, interpret=interpret,
        emit_stored=emit_stored, emit_cov=emit_cov, batched=batched)
    if batched:
        xo = xo[:, :b, :u].astype(orig_dtype)
        if s is not None:
            s = s[:, :, :b, :u].astype(orig_dtype)
        if cov is not None:
            cov = cov[:, :b, :u]
        # [C, gi, gj, bm, P] -> sum universe tiles -> [C, m_pad, P] -> trim
        cnt = cnt.sum(axis=2).reshape(c, m_pad, p)[:, :b]
        dsz = dsz.sum(axis=2).reshape(c, m_pad, p)[:, :b]
        return xo, s, cov, cnt, dsz
    xo = xo[:b, :u].astype(orig_dtype)
    if s is not None:
        s = s[:, :b, :u].astype(orig_dtype)
    if cov is not None:
        cov = cov[:b, :u]
    # [gi, gj, bm, P] -> sum universe tiles -> [m_pad, P] -> trim pad nodes
    cnt = cnt.sum(axis=1).reshape(m_pad, p)[:b]
    dsz = dsz.sum(axis=1).reshape(m_pad, p)[:b]
    return xo, s, cov, cnt, dsz


# -- single-launch sync round (megakernel, DESIGN.md §17) ---------------------

def _routes_for(nbrs, rev):
    """Static routing table for the megakernel: routes[q][n] =
    (sender_slot, sender_node) realizing inbox[n, q] = d_all[nbrs[n, q],
    rev[n, q]]. Topology padding slots route to (0, 0) — inert under the
    kernel's active mask."""
    import numpy as np

    nbrs = np.asarray(nbrs)
    rev = np.asarray(rev)
    n, p = nbrs.shape
    return tuple(
        tuple((int(rev[i, q]), int(nbrs[i, q])) for i in range(n))
        for q in range(p))


def _lane_tiles(u: int):
    """Universe tile widths for the megakernel: lane multiples below u,
    and u itself (a block spanning the whole axis needs no lane padding —
    the store's short objects keep their unpadded width in HBM)."""
    return sorted({w for w in (128, 256, 512, 1024, 2048) if w < u}
                  | ({u} if u <= 2048 else set()))


def sync_round_block(b: int, n: int, u: int, *, p: int, k: int,
                     kind: str = "max", layout: str = "grid",
                     interpret=None, tune_bench=None, n_planes: int = 1):
    """Resolve the megakernel tile config (g, bn) for the given shapes —
    autotuned (kernels.common.tuned_block) with a heuristic default.

    ``b``: configs, ``n``: nodes, ``u``: flattened universe, ``p``: degree,
    ``k``: buffer slots (0 = state-based), ``n_planes``: planes a point (a
    lex point's 1 + value planes). Every tile holds the whole node axis.
    Points of more than two planes take one config a tile and the widest
    lane tile that divides ``u`` and whose blocks fit ``VMEM_BUDGET``.
    Returns ``((g, bn), source)``.
    """
    interpret = interpret_default() if interpret is None else interpret
    np_ = -(-n // SUBLANE) * SUBLANE             # VMEM rows per config
    bn_opts = _lane_tiles(u)
    if n_planes > 2:
        fits = [w for w in bn_opts if u % w == 0 and tile_bytes(
            n_planes, k, p, 1, n, w, True) <= VMEM_BUDGET]
        default = (1, max(fits) if fits else min(bn_opts))
        key = (common.backend_key(), kind, f"a{n_planes}", f"p{p}", f"k{k}",
               layout, f"n{np_}", f"b{common.shape_bucket(b)}",
               f"u{common.shape_bucket(u)}")
        return common.tuned_block("round_step", key,
                                  [default] + [(1, w) for w in fits
                                               if (1, w) != default],
                                  tune_bench)
    if layout == "rows" and b > 1:
        g_opts = sorted({min(b, g) for g in (1, max(1, 64 // np_),
                                             max(1, 256 // np_))})
        g_default = min(b, max(1, 64 // np_))
    else:
        g_opts, g_default = [1], 1
    default = (g_default, u if u <= 1024 else 1024)
    cands = [default] + [(g, bn) for g in g_opts for bn in bn_opts
                         if (g, bn) != default]
    key = (common.backend_key(), kind, f"p{p}", f"k{k}", layout, f"n{np_}",
           f"b{common.shape_bucket(b)}", f"u{common.shape_bucket(u)}")
    return common.tuned_block("round_step", key, cands, tune_bench)


def sync_round(delta, x, buf, active, delivered, *, nbrs, rev,
               kind: str = "max", per_origin: bool = False,
               extracts: bool = False, want_inbox: bool = False,
               layout: str = "grid", block=None, interpret=None):
    """One full Algorithm 1/2 sync round in a single kernel launch
    (DESIGN.md §17). Canonical operands:

    * ``delta``/``x``: [B, N, U] (B=1 for unbatched runs)
    * ``buf``: [K, B, N, U] slot-major origin buffer (K = P+1 per-origin,
      1 flat) or None for state-based sync
    * ``active``: [B, N, P] bool/int per-(node, slot) receive mask
    * ``delivered``: [B, N] bool/int ack mask (buffer cleared where 1);
      ignored without a buffer
    * ``nbrs``/``rev``: the topology's static [N, P] routing tables

    ``kind="lex"`` takes ``delta``, ``x`` and ``buf`` as (t, v₁, …) tuples
    of such arrays and returns x', buf' and the inbox as such tuples.

    Returns ``(x', buf', inbox, dsz_op, xsz, ssend, cnt, dsz)``: states and
    buffers in the input dtype; ``inbox`` [P, B, N, U] — the active-masked
    received δ-groups, emitted for the classic/bp flavors
    (``buf is not None and not extracts``) whose keep-gate needs the global
    count, and whenever ``want_inbox`` forces it (provenance replay,
    DESIGN.md §19 — orthogonal to ``extracts``, so an RR flavor keeps its
    in-kernel Δ-merge while also emitting the inbox), else None;
    ``dsz_op``/``xsz`` int32 [B, N] (local-δ and final state sizes);
    ``ssend``/``cnt``/``dsz`` int32 [B, N, P] (send sizes before liveness
    masking, novel counts, received sizes).
    """
    interpret = interpret_default() if interpret is None else interpret
    b, n, u = planes(x, kind)[0].shape
    p = nbrs.shape[-1]
    has_buffer = buf is not None
    k = planes(buf, kind)[0].shape[0] if has_buffer else 0
    emit_inbox = (has_buffer and not extracts) or want_inbox
    if block is None:
        block, _ = sync_round_block(b, n, u, p=p, k=k, kind=kind,
                                    layout=layout, interpret=interpret,
                                    n_planes=len(planes(x, kind)))
    g, bn = block
    g = max(1, min(g, b))
    bn = min(bn, u)
    b_pad = -(-b // g) * g
    u_pad = -(-u // bn) * bn
    routes = _routes_for(nbrs, rev)

    dtypes = [c.dtype for c in planes(x, kind)]

    def pad(v, lead=()):
        return point([jnp.pad(c.astype(BOOL_VIEW if c.dtype == jnp.bool_
                                       else c.dtype),
                              lead + ((0, b_pad - b), (0, 0), (0, u_pad - u)))
                      for c in planes(v, kind)], kind)

    d2, x2 = pad(delta), pad(x)
    if has_buffer:
        b2 = pad(buf, ((0, 0),))
        dlv = jnp.pad(delivered.astype(jnp.int32), ((0, b_pad - b), (0, 0)))
    else:
        b2, dlv = None, None
    a2 = jnp.pad(active.astype(jnp.int32), ((0, b_pad - b), (0, 0), (0, 0)))

    xo, bo, ib, nodecnt, ssend, cnt, dsz = round_step_2d(
        d2, x2, b2, a2, dlv, routes=routes, kind=kind,
        per_origin=per_origin, emit_inbox=emit_inbox,
        extracts=bool(extracts and has_buffer), block=(g, bn),
        interpret=interpret)

    def unpad(v, lead=()):
        cut = (slice(None),) * len(lead) + (slice(b), slice(n), slice(u))
        return point([c[cut].astype(dt)
                      for c, dt in zip(planes(v, kind), dtypes)], kind)

    xo = unpad(xo)
    if bo is not None:
        bo = unpad(bo, ((0, 0),))
    if ib is not None:
        ib = unpad(ib, ((0, 0),))

    def trim(c):
        # [GB, GJ, g, N, C] -> sum universe tiles -> [B, N, C]
        t = c.sum(axis=1, dtype=jnp.int32)
        return t.reshape((b_pad, n) + t.shape[3:])[:b]

    nodecnt = trim(nodecnt)
    return (xo, bo, ib, nodecnt[..., 0], nodecnt[..., 1],
            trim(ssend), trim(cnt), trim(dsz))


# -- digest subsystem (DESIGN.md §14) ----------------------------------------

def digest_blocks(x, *, block_elems: int, kind: str = "max", interpret=None,
                  batched: bool = False, layout: str = "grid"):
    """Blockwise digest of dense states x [(B,) N, U] -> uint32
    [(B,) N, nB, 3] with channels [hash, count, agg] — bit-identical to
    ``sync.digest.digest_state`` on single-array states (same mixing
    constants; all arithmetic is order-independent mod 2^32).

    ``batched=True`` declares the leading config axis B (DESIGN.md §13),
    which becomes the kernel's leading batch grid dimension — or folds
    into the tile row axis with ``layout="rows"`` (store engine, §15);
    per-row digests are independent, so both layouts are bit-identical.
    """
    interpret = interpret_default() if interpret is None else interpret
    if batched and layout == "rows":
        b, n, u = x.shape
        out = digest_blocks(x.reshape(b * n, u), block_elems=block_elems,
                            kind=kind, interpret=interpret)
        return out.reshape((b, n) + out.shape[1:])
    m, u = x.shape[-2], x.shape[-1]
    nb = -(-u // block_elems)
    block = digest_tile(u, block_elems)
    bm, nb_t = block
    m_pad = -(-m // bm) * bm
    n_pad = -(-nb // nb_t) * nb_t * block_elems
    lead = ((0, 0),) if batched else ()
    v = jnp.pad(x.astype(jnp.uint32),
                lead + ((0, m_pad - m), (0, n_pad - u)))
    h, c, a = digest_blocks_2d(v, be=block_elems, kind=kind, block=block,
                               interpret=interpret, batched=batched)
    out = jnp.stack([h, c, a], axis=-1)          # [(B,) m_pad, NBpad, 3]
    return out[..., :m, :nb, :]


def masked_extract(x, block_masks, *, block_elems: int, interpret=None,
                   batched: bool = False, layout: str = "grid"):
    """Per-slot Δ(state, block_mask): x [(B,) N, U] restricted to each
    slot's masked blocks. ``block_masks`` bool [(B,) N, P, nB]; returns
    [(B,) N, P, U] in x's dtype with the x tile read once for all P slots.
    ``layout="rows"`` folds a batched config axis into the tile rows
    (store engine, DESIGN.md §15) — bit-identical to the batch grid.
    """
    interpret = interpret_default() if interpret is None else interpret
    if batched and layout == "rows":
        b, n, u = x.shape
        out = masked_extract(
            x.reshape(b * n, u),
            block_masks.reshape((b * n,) + block_masks.shape[2:]),
            block_elems=block_elems, interpret=interpret)
        return out.reshape((b, n) + out.shape[1:])
    m, u = x.shape[-2], x.shape[-1]
    p = block_masks.shape[-2]
    nb = -(-u // block_elems)
    assert block_masks.shape[-1] == nb
    block = digest_tile(u, block_elems)
    bm, nb_t = block
    m_pad = -(-m // bm) * bm
    nb_pad = -(-nb // nb_t) * nb_t
    n_pad = nb_pad * block_elems
    orig_dtype = x.dtype
    if orig_dtype == jnp.bool_:
        x = x.astype(BOOL_VIEW)
    lead = ((0, 0),) if batched else ()
    x2 = jnp.pad(x, lead + ((0, m_pad - m), (0, n_pad - u)))
    # [(B,) N, P, nB] -> [P, (B,) N_pad, nB_pad] int32
    mk = jnp.moveaxis(block_masks.astype(jnp.int32), -2, 0)
    mk = jnp.pad(mk, ((0, 0),) + lead + ((0, m_pad - m), (0, nb_pad - nb)))
    out = masked_extract_2d(x2, mk, be=block_elems, block=block,
                            interpret=interpret, batched=batched)
    out = out[..., :m, :u]                        # [P, (B,) N, U]
    return jnp.moveaxis(out, 0, -2).astype(orig_dtype)


# -- bit-packed GSet helpers (beyond-paper wire/memory format) ---------------

def pack_bits(mask: jnp.ndarray) -> jnp.ndarray:
    """bool[..., U] -> uint32[..., ceil(U/32)] little-endian bit packing."""
    u = mask.shape[-1]
    pad = (-u) % 32
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, pad)])
    m = m.reshape(mask.shape[:-1] + (-1, 32)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))
    return jnp.sum(m * weights, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jnp.ndarray, universe: int) -> jnp.ndarray:
    bits = (words[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (-1,))[..., :universe].astype(jnp.bool_)
