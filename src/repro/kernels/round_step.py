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

from repro.kernels.common import interpret_default, pallas_call


def _count_rows(v, kind: str):
    """Per-row irreducible count over the lane axis, pinned int32 (jnp.sum
    would promote under the simulator's x64 metric context)."""
    if kind == "max":
        return jnp.sum((v != 0).astype(jnp.int32), axis=-1, dtype=jnp.int32)
    return jnp.sum(jax.lax.population_count(v).astype(jnp.int32), axis=-1,
                   dtype=jnp.int32)


def _round_step_kernel(d_ref, x_ref, *refs, g: int, np_: int, p: int, k: int,
                       kind: str, per_origin: bool, emit_inbox: bool,
                       extracts: bool, routes):
    has_buffer = k > 0
    refs = list(refs)
    buf_ref = refs.pop(0) if has_buffer else None
    act_ref = refs.pop(0)
    xo_ref = refs.pop(0)
    bo_ref = refs.pop(0) if has_buffer else None
    ib_ref = refs.pop(0) if emit_inbox else None
    nc_ref, ss_ref, cnt_ref, dsz_ref = refs

    op = jnp.maximum if kind == "max" else jnp.bitwise_or
    zero = jnp.zeros((), x_ref.dtype)

    # (1) local update: δ joins into x and the self slot  [Alg 2, lines 6-8]
    x = x_ref[...]                                         # [g, N, bn]
    d0 = d_ref[...]
    nc_ref[0, 0, :, :, 0] = _count_rows(d0, kind)          # |⇓δ| per node
    x = op(x, d0)
    if has_buffer:
        slots = [buf_ref[i] for i in range(k)]
        slots[k - 1 if per_origin else 0] = \
            op(slots[k - 1 if per_origin else 0], d0)

    # (2) sends                                           [Alg 2, lines 9-12]
    if not has_buffer:                                     # state-based
        sends = [x] * p
    elif per_origin:                                       # bp/bprr: loo fold
        zt = jnp.zeros_like(x)
        prefix, suffix = [zt] * k, [zt] * k
        acc = zt
        for i in range(k):
            prefix[i] = acc
            acc = op(acc, slots[i])
        acc = zt
        for i in range(k - 1, -1, -1):
            suffix[i] = acc
            acc = op(acc, slots[i])
        sends = [op(prefix[j], suffix[j]) for j in range(p)]
    else:                                                  # classic/rr: bcast
        sends = [slots[0]] * p
    for j in range(p):
        ss_ref[0, 0, :, :, j] = _count_rows(sends[j], kind)

    # Masks are int32 columns compared after the lane broadcast: Mosaic
    # cannot reshape an i1 vector to add the lane axis.
    act = act_ref[...]                          # [g, N, P (+1: delivered)]

    def column(c):
        return jnp.broadcast_to(act[:, :, c:c + 1], x.shape)

    # (3) ack-gated buffer clear                          [Alg 2, line 13]
    if has_buffer:
        retain = column(p) == 0
        slots = [jnp.where(retain, s, zero) for s in slots]

    # (4) route + receive all P slots in order            [Alg 2, lines 14-17]
    for q in range(p):
        # Static routing: inbox[n] = sends[rev[n,q]] of node nbrs[n,q].
        # Topology padding slots route to (0, 0), masked off below.
        dq = jnp.stack(
            [sends[routes[q][n][0]][:, routes[q][n][1], :]
             for n in range(np_)], axis=1)                 # [g, N, bn]
        d = jnp.where(column(q) != 0, dq, zero)
        if kind == "max":
            novel = d > x
            s = jnp.where(novel, d, zero)
            cnt = jnp.sum(novel, axis=-1, dtype=jnp.int32)
            x = jnp.maximum(x, d)
        else:
            s = jnp.bitwise_and(d, jnp.bitwise_not(x))
            cnt = _count_rows(s, kind)
            x = jnp.bitwise_or(x, d)
        cnt_ref[0, 0, :, :, q] = cnt
        dsz_ref[0, 0, :, :, q] = _count_rows(d, kind)
        if emit_inbox:                  # classic/bp keep-gate is global; also
            ib_ref[q] = d               # provenance replay (want_inbox)
        if extracts:                    # rr/bprr: Δ is ⊥ where not novel
            slots[q if per_origin else 0] = op(slots[q if per_origin else 0],
                                               s)

    xo_ref[...] = x
    nc_ref[0, 0, :, :, 1] = _count_rows(x, kind)           # |⇓x'| per node
    if has_buffer:
        for i in range(k):
            bo_ref[i] = slots[i]


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
    b, np_, u = x.shape
    assert delta.shape == x.shape and delta.dtype == x.dtype
    g, bn = block
    assert b % g == 0 and u % bn == 0
    grid = (b // g, u // bn)
    gb, gj = grid
    has_buffer = buf is not None
    k = buf.shape[0] if has_buffer else 0
    if extracts is None:
        extracts = has_buffer and not emit_inbox
    assert not (extracts and not has_buffer)

    d_spec = pl.BlockSpec((g, np_, bn), lambda i, j: (i, 0, j))
    nc_spec = pl.BlockSpec((1, 1, g, np_, 2), lambda i, j: (i, j, 0, 0, 0))
    sl_spec = pl.BlockSpec((1, 1, g, np_, p), lambda i, j: (i, j, 0, 0, 0))
    nc_shape = jax.ShapeDtypeStruct((gb, gj, g, np_, 2), jnp.int32)
    sl_shape = jax.ShapeDtypeStruct((gb, gj, g, np_, p), jnp.int32)

    in_specs = [d_spec, d_spec]
    args = [delta, x]
    if has_buffer:
        b_spec = pl.BlockSpec((k, g, np_, bn), lambda i, j: (0, i, 0, j))
        in_specs.append(b_spec)
        args.append(buf)
    act = active.astype(jnp.int32)
    if has_buffer:
        act = jnp.concatenate(
            [act, delivered.astype(jnp.int32)[:, :, None]], axis=-1)
    in_specs.append(pl.BlockSpec((g, np_, act.shape[-1]),
                                 lambda i, j: (i, 0, 0)))
    args.append(act)

    out_specs = [d_spec]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if has_buffer:
        out_specs.append(b_spec)
        out_shape.append(jax.ShapeDtypeStruct(buf.shape, buf.dtype))
    if emit_inbox:
        ib_spec = pl.BlockSpec((p, g, np_, bn), lambda i, j: (0, i, 0, j))
        out_specs.append(ib_spec)
        out_shape.append(jax.ShapeDtypeStruct((p,) + x.shape, x.dtype))
    out_specs += [nc_spec, sl_spec, sl_spec, sl_spec]
    out_shape += [nc_shape, sl_shape, sl_shape, sl_shape]

    # x' and buf' overwrite x and buf in place: each grid step reads its
    # blocks before writing the same blocks back, and a store's buffer
    # stack is too large to hold twice in HBM.
    aliases = {1: 0, 2: 1} if has_buffer else {1: 0}
    outs = pallas_call(
        functools.partial(_round_step_kernel, g=g, np_=np_, p=p, k=k,
                          kind=kind, per_origin=per_origin,
                          emit_inbox=emit_inbox, extracts=extracts,
                          routes=routes),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name="round_step",
    )(*args)

    outs = list(outs)
    xo = outs.pop(0)
    bo = outs.pop(0) if has_buffer else None
    ib = outs.pop(0) if emit_inbox else None
    nodecnt, ssend, cnt, dsz = outs
    return xo, bo, ib, nodecnt, ssend, cnt, dsz
