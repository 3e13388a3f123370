"""Single-launch sync-round megakernel (DESIGN.md §17).

One ``pallas_call`` executes an ENTIRE Algorithm 1/2 round for the dense
delta family (state / classic / bp / rr / bprr): local δ-join, origin-slot
buffering, the per-neighbor sends (leave-one-out fold for BP), ack-gated
buffer clearing, the static inbox routing, and the P-slot slot-order
receive — replacing the ``delta_extract`` → ``buffer_fold`` →
``round_recv`` chain, whose intermediates (sends, gathered inbox, stored
extractions) each made an HBM round trip between launches. Here they are
values in VMEM: a (config, node, universe) tile loads x, δ, and the K
buffer slots once, runs the whole round on them, and writes back x', the
K updated slots, and the per-(node, slot) counts the metric epilogue needs.

The trick that makes in-kernel *routing* possible: the topology's
``nbrs``/``rev`` tables are trace-time constants ([N, P] numpy, N small),
so ``inbox[n, q] = send[nbrs[n,q]][rev[n,q]]`` unrolls into N·P static row
selects over the send values already in VMEM — the whole node axis rides
inside every tile, and the gather that previously streamed the [N, P, U]
send block through HBM disappears.

Tile layout [g, N, bn]: N = the whole node axis (required for routing; a
block spanning a whole axis needs no sublane padding); bn = universe lanes,
a multiple of 128 or the whole universe; g = configs per tile.
g=1 serves unbatched runs and the sweep engine's "grid" layout (one config
per batch-grid step); g>1 folds the store engine's many small objects into
tall tiles ("rows" layout) — per-config programs are identical either way,
so both layouts are bit-identical (DESIGN.md §13/§15 invariant).

Receive semantics exactly mirror ``round_recv``'s slot-order fold: novelty
is judged against the RUNNING state, counts are per grid block (wrapper
sums the universe-tile axis), and the active mask (topology padding ∧
fault delivery) suppresses a slot entirely. RR flavors merge their Δ
extractions into the cleared buffer in-kernel (extractions are already ⊥
where not novel, so the merge is unconditional); classic/bp flavors need
the *global* inflation check cnt > 0 (a reduction over all universe
tiles), so the kernel emits the active-masked inbox and the engine applies
the keep-gated merge in a jnp epilogue — same structure as the fused
engine, minus the separate routing/receive launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.value_lattices import lex_gt
from repro.kernels.common import SUBLANE, interpret_default, pallas_call

# Scoped VMEM: what a launch gets unless it asks (v5e), and the most a
# launch asks for (of the core's 128 MiB).
VMEM_DEFAULT = 16 << 20
VMEM_MAX = 100 << 20


def planes(v, kind: str) -> tuple:
    """The planes of a state operand of ``kind``: a lex point's (t, v₁, …),
    or ``(v,)``."""
    return tuple(v) if kind == "lex" else (v,)


def point(cs, kind: str):
    """The state operand of ``kind`` made of the planes ``cs`` (the inverse
    of :func:`planes`)."""
    return tuple(cs) if kind == "lex" else cs[0]


def tile_bytes(a: int, k: int, p: int, g: int, n: int, bn: int,
               emit_inbox: bool) -> int:
    """VMEM bytes of one grid step's state blocks, double-buffered: δ, x
    and the K buffer slots in, x', the K slots and (``emit_inbox``) the P
    inbox slots out, each ``a`` planes of [g, N, bn] 32-bit words with N
    padded to the sublane."""
    blocks = a * (3 + 2 * k + (p if emit_inbox else 0))
    return 2 * blocks * g * (-(-n // SUBLANE) * SUBLANE) * bn * 4


def _nonbottom(v):
    """Where a lex point is not ⊥: some plane is nonzero."""
    out = v[0] != 0
    for c in v[1:]:
        out = out | (c != 0)
    return out


def _count_rows(v, kind: str):
    """Per-row irreducible count of a point (a tuple of planes) over the
    lane axis, pinned int32 (jnp.sum would promote under the simulator's
    x64 metric context). A lex point counts where any plane ≠ 0."""
    if kind == "max":
        return jnp.sum((v[0] != 0).astype(jnp.int32), axis=-1,
                       dtype=jnp.int32)
    if kind == "bitor":
        return jnp.sum(jax.lax.population_count(v[0]).astype(jnp.int32),
                       axis=-1, dtype=jnp.int32)
    return jnp.sum(_nonbottom(v).astype(jnp.int32), axis=-1, dtype=jnp.int32)


def _join(a, b, kind: str):
    """Pointwise join of two points (tuples of planes). A lex join selects
    every plane of the larger point by one mask."""
    if kind == "max":
        return (jnp.maximum(a[0], b[0]),)
    if kind == "bitor":
        return (jnp.bitwise_or(a[0], b[0]),)
    win = lex_gt(a, b)
    return tuple(jnp.where(win, ca, cb) for ca, cb in zip(a, b))


def _where(mask, a):
    """``a`` where ``mask``, ⊥ (0 in every plane) elsewhere."""
    return tuple(jnp.where(mask, c, jnp.zeros((), c.dtype)) for c in a)


def _receive(d, x, kind: str):
    """One receive slot: ``(Δ(d, x), |⇓Δ| per row, x ⊔ d)``. Novelty is
    ¬(d ⊑ x) on a non-⊥ slot of d, in the kind's order."""
    if kind == "max":
        novel = d[0] > x[0]
        s = _where(novel, d)
        cnt = jnp.sum(novel, axis=-1, dtype=jnp.int32)
        return s, cnt, (jnp.maximum(x[0], d[0]),)
    if kind == "bitor":
        s = (jnp.bitwise_and(d[0], jnp.bitwise_not(x[0])),)
        return s, _count_rows(s, kind), (jnp.bitwise_or(x[0], d[0]),)
    above = lex_gt(d, x)                  # x ⊔ d is d here, else x
    novel = above & _nonbottom(d)
    cnt = jnp.sum(novel, axis=-1, dtype=jnp.int32)
    return (_where(novel, d), cnt,
            tuple(jnp.where(above, cd, cx) for cd, cx in zip(d, x)))


def _round_step_kernel(*refs, g: int, np_: int, p: int, k: int, kind: str,
                       a: int, per_origin: bool, emit_inbox: bool,
                       extracts: bool, routes):
    # a: planes a point (1, or a lex point's 1 + value planes)
    has_buffer = k > 0
    refs = list(refs)

    def take(n):
        out = refs[:n]
        del refs[:n]
        return out

    d_refs, x_refs = take(a), take(a)
    buf_refs = take(a) if has_buffer else None
    (act_ref,) = take(1)
    xo_refs = take(a)
    bo_refs = take(a) if has_buffer else None
    ib_refs = take(a) if emit_inbox else None
    nc_ref, ss_ref, cnt_ref, dsz_ref = refs

    def join(u, v):
        return _join(u, v, kind)

    # (1) local update: δ joins into x and the self slot  [Alg 2, lines 6-8]
    x = tuple(r[...] for r in x_refs)                      # [g, N, bn] each
    d0 = tuple(r[...] for r in d_refs)
    nc_ref[0, 0, :, :, 0] = _count_rows(d0, kind)          # |⇓δ| per node
    x = join(x, d0)
    if has_buffer:
        slots = [tuple(r[i] for r in buf_refs) for i in range(k)]
        own = k - 1 if per_origin else 0
        slots[own] = join(slots[own], d0)

    # (2) sends                                           [Alg 2, lines 9-12]
    if not has_buffer:                                     # state-based
        sends = [x] * p
    elif per_origin:                                       # bp/bprr: loo fold
        zt = tuple(jnp.zeros_like(c) for c in x)
        prefix, suffix = [zt] * k, [zt] * k
        acc = zt
        for i in range(k):
            prefix[i] = acc
            acc = join(acc, slots[i])
        acc = zt
        for i in range(k - 1, -1, -1):
            suffix[i] = acc
            acc = join(acc, slots[i])
        sends = [join(prefix[j], suffix[j]) for j in range(p)]
    else:                                                  # classic/rr: bcast
        sends = [slots[0]] * p
    for j in range(p):
        ss_ref[0, 0, :, :, j] = _count_rows(sends[j], kind)

    # Masks are int32 columns compared after the lane broadcast: Mosaic
    # cannot reshape an i1 vector to add the lane axis.
    act = act_ref[...]                          # [g, N, P (+1: delivered)]

    def column(c):
        return jnp.broadcast_to(act[:, :, c:c + 1], x[0].shape)

    # (3) ack-gated buffer clear                          [Alg 2, line 13]
    if has_buffer:
        retain = column(p) == 0
        slots = [_where(retain, sl) for sl in slots]

    # (4) route + receive all P slots in order            [Alg 2, lines 14-17]
    for q in range(p):
        # Static routing: inbox[n] = sends[rev[n,q]] of node nbrs[n,q].
        # Topology padding slots route to (0, 0), masked off below.
        dq = tuple(
            jnp.stack([sends[routes[q][n][0]][c][:, routes[q][n][1], :]
                       for n in range(np_)], axis=1)       # [g, N, bn]
            for c in range(a))
        d = _where(column(q) != 0, dq)
        s, cnt, x = _receive(d, x, kind)
        cnt_ref[0, 0, :, :, q] = cnt
        dsz_ref[0, 0, :, :, q] = _count_rows(d, kind)
        if emit_inbox:                  # classic/bp keep-gate is global; also
            for r, c in zip(ib_refs, d):  # provenance replay (want_inbox)
                r[q] = c
        if extracts:                    # rr/bprr: Δ is ⊥ where not novel
            tgt = q if per_origin else 0
            slots[tgt] = join(slots[tgt], s)

    for r, c in zip(xo_refs, x):
        r[...] = c
    nc_ref[0, 0, :, :, 1] = _count_rows(x, kind)           # |⇓x'| per node
    if has_buffer:
        for i in range(k):
            for r, c in zip(bo_refs, slots[i]):
                r[i] = c


@functools.partial(
    jax.jit,
    static_argnames=("routes", "kind", "per_origin", "emit_inbox", "extracts",
                     "block", "interpret"))
def round_step_2d(delta, x, buf, active, delivered, *, routes,
                  kind: str = "max", per_origin: bool = False,
                  emit_inbox: bool = False, extracts: bool | None = None,
                  block=(1, 512), interpret: bool | None = None):
    """One full sync round over tile-aligned canonical operands.

    ``delta``/``x``: [B, N, U] (B a multiple of g, N the whole node axis,
    U a multiple of bn); ``buf``: [K, B, N, U] or None; ``active``: int32
    [B, N, P]; ``delivered``: int32 [B, N] or None (required iff buf is
    given). The kernel reads ``delivered`` as a last column of ``active``:
    a [g, N] block of its own would break the (8, 128) block rule whenever
    g < B. ``x`` and ``buf`` are updated in place (aliased to x', buf').
    ``routes``: static tuple-of-tuples, routes[q][n] = (sender_slot,
    sender_node) realizing inbox[n, q] = d_all[nbrs[n,q], rev[n,q]].
    ``block`` = (g, bn).

    ``kind="lex"`` takes every state operand and result (``delta``, ``x``,
    ``buf``, x', buf', inbox) as a tuple (t, v₁, …) of such arrays, joined
    lexicographically (a record's value planes ride with its timestamp);
    its launch is named ``round_step_lex``, the others ``round_step``.

    ``extracts`` merges the slot-order Δ extractions into the buffer
    in-kernel (rr/bprr). Historically it was the complement of
    ``emit_inbox``; it is independent now so provenance can request the
    masked inbox (``emit_inbox=True``) without silently disabling an RR
    flavor's in-kernel merge. None keeps the legacy derivation
    ``has_buffer and not emit_inbox``.

    Returns ``(x', buf', inbox, nodecnt, ssend, cnt, dsz)``:
    buf' [K, B, N, U] (None without buffer), inbox [P, B, N, U] (None
    unless ``emit_inbox``), nodecnt [GB, GJ, g, N, 2] int32 with channels
    (|⇓δ|, |⇓x'|), and ssend/cnt/dsz [GB, GJ, g, N, P] per-block counts —
    sum the GJ axis for totals.
    """
    interpret = interpret_default() if interpret is None else interpret
    p = len(routes)
    dts, xs = planes(delta, kind), planes(x, kind)
    a = len(xs)
    b, np_, u = xs[0].shape
    assert all(c.shape == xs[0].shape for c in dts + xs)
    assert [c.dtype for c in dts] == [c.dtype for c in xs]
    g, bn = block
    assert b % g == 0 and u % bn == 0
    grid = (b // g, u // bn)
    gb, gj = grid
    has_buffer = buf is not None
    bufs = planes(buf, kind) if has_buffer else ()
    k = bufs[0].shape[0] if has_buffer else 0
    if extracts is None:
        extracts = has_buffer and not emit_inbox
    assert not (extracts and not has_buffer)

    d_spec = pl.BlockSpec((g, np_, bn), lambda i, j: (i, 0, j))
    nc_spec = pl.BlockSpec((1, 1, g, np_, 2), lambda i, j: (i, j, 0, 0, 0))
    sl_spec = pl.BlockSpec((1, 1, g, np_, p), lambda i, j: (i, j, 0, 0, 0))
    nc_shape = jax.ShapeDtypeStruct((gb, gj, g, np_, 2), jnp.int32)
    sl_shape = jax.ShapeDtypeStruct((gb, gj, g, np_, p), jnp.int32)

    in_specs = [d_spec] * (2 * a)
    args = list(dts + xs)
    if has_buffer:
        b_spec = pl.BlockSpec((k, g, np_, bn), lambda i, j: (0, i, 0, j))
        in_specs += [b_spec] * a
        args += bufs
    act = active.astype(jnp.int32)
    if has_buffer:
        act = jnp.concatenate(
            [act, delivered.astype(jnp.int32)[:, :, None]], axis=-1)
    in_specs.append(pl.BlockSpec((g, np_, act.shape[-1]),
                                 lambda i, j: (i, 0, 0)))
    args.append(act)

    out_specs = [d_spec] * a
    out_shape = [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in xs]
    if has_buffer:
        out_specs += [b_spec] * a
        out_shape += [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in bufs]
    if emit_inbox:
        ib_spec = pl.BlockSpec((p, g, np_, bn), lambda i, j: (0, i, 0, j))
        out_specs += [ib_spec] * a
        out_shape += [jax.ShapeDtypeStruct((p,) + c.shape, c.dtype)
                      for c in xs]
    out_specs += [nc_spec, sl_spec, sl_spec, sl_spec]
    out_shape += [nc_shape, sl_shape, sl_shape, sl_shape]

    # Wide lex points outgrow the default scoped VMEM: ask for room for the
    # blocks and as much again for the values the round holds (sends, the
    # leave-one-out prefixes and suffixes).
    need = tile_bytes(a, k, p, g, np_, bn, emit_inbox)
    extra = {}
    if need > VMEM_DEFAULT // 2:
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=min(VMEM_MAX, 3 * need))

    # x' and buf' overwrite x and buf in place: each grid step reads its
    # blocks before writing the same blocks back, and a store's buffer
    # stack is too large to hold twice in HBM.
    aliases = {a + i: i for i in range(a)}
    if has_buffer:
        aliases.update({2 * a + i: a + i for i in range(a)})
    outs = pallas_call(
        functools.partial(_round_step_kernel, g=g, np_=np_, p=p, k=k,
                          kind=kind, a=a, per_origin=per_origin,
                          emit_inbox=emit_inbox, extracts=extracts,
                          routes=routes),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="round_step_lex" if kind == "lex" else "round_step",
        **extra,
    )(*args)

    outs = list(outs)

    def take():
        out = outs[:a]
        del outs[:a]
        return point(out, kind)

    xo = take()
    bo = take() if has_buffer else None
    ib = take() if emit_inbox else None
    nodecnt, ssend, cnt, dsz = outs
    return xo, bo, ib, nodecnt, ssend, cnt, dsz
