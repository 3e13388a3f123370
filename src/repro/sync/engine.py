"""Sync-round engine dispatch: reference jnp loop vs fused Pallas kernels.

DESIGN.md §11/§17. Three engines execute one synchronous round:

* ``reference`` — the pure-jnp sequential slot loop in
  ``SyncAlgorithm.round_step`` (3+ HBM passes over the [N, U] state per
  neighbor slot, P slots per round).
* ``fused``     — the receive phase runs as ONE tiled pass via
  ``kernels.round_recv`` (state tile VMEM-resident across all P slots) and
  the BP leave-one-out sends fold through ``kernels.buffer_fold``.
* ``mega``      — the ENTIRE delta-family round (local join, buffering,
  leave-one-out sends, ack-gated clear, static routing, P-slot receive)
  runs as a single ``kernels.round_step`` launch; the fused engine's
  remaining inter-kernel HBM round trips (sends, gathered inbox, stored
  extractions) become VMEM-resident values. The resync modes (state_driven/
  digest_driven) take the fused per-phase kernels under ``mega``.

Dispatch is by ``Lattice.kernel_kind``: lattices whose join/Δ have a dense
single-array kernel ("max", "bitor") can run fused/mega; lex maps ("lex":
LWW maps, lex counters) run ``mega``'s delta family, whose kernel holds
every plane of a (t, v₁, …) point; everything else (products, linear sums,
and lex maps on ``fused`` or in the resync modes) falls back to the reference
engine, so ``engine="fused"``/``"mega"`` is always safe to request —
``resolve`` says which engine runs.

All engines are bit-identical in final states, buffers, and metrics: max/or
folds are exact and the kernels preserve Algorithm 2's slot-order
semantics (Δ against the *running* state). The engine-equivalence test suite
asserts this across every algorithm × lattice × topology combination.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import common as kcommon
from repro.kernels import ops as kops

ENGINES = ("reference", "fused", "mega")

# Engines that dispatch to the Pallas kernels (vs the pure-jnp reference).
KERNEL_ENGINES = ("fused", "mega")

# Kernel kinds the fused per-phase kernels implement end-to-end.
FUSED_KINDS = ("max", "bitor")
# ... and the megakernel's round (the delta family: state/classic/bp/rr/bprr).
MEGA_KINDS = FUSED_KINDS + ("lex",)


def supports_fused(lattice) -> bool:
    """A lattice runs the fused per-phase kernels iff its state is one
    dense array with a kernel kind."""
    return getattr(lattice, "kernel_kind", None) in FUSED_KINDS


def resolve(engine: str, lattice, *, resync: bool) -> str:
    """Validate ``engine`` and apply the automatic jnp fallback: the engine
    that runs. ``resync``: whether the algorithm is an anti-entropy mode,
    which takes the fused per-phase kernels under ``mega`` too (DESIGN.md
    §14), so a lex lattice runs ``reference`` there."""
    if engine not in ENGINES:
        raise ValueError(
            f"unknown engine {engine!r}; expected one of {ENGINES}")
    kind = getattr(lattice, "kernel_kind", None)
    kinds = MEGA_KINDS if engine == "mega" and not resync else FUSED_KINDS
    if engine in KERNEL_ENGINES and kind not in kinds:
        return "reference"
    return engine


def gather_inbox(d_all, topo, batched: bool = False):
    """Route per-edge messages: inbox[n, q] = d_all[nbrs[n,q], rev[n,q]].

    One gather pass over the [N, P, U] send block — the fused engine's only
    data movement before the single kernel pass. Padding slots carry
    garbage (node 0's sends); the kernel's active-slot mask suppresses
    them in VMEM, saving the extra masking pass over HBM.

    With ``batched=True`` the send block carries a leading config axis
    ([B, N, P, U], DESIGN.md §13) and the same shared-topology gather is
    applied to every config.
    """
    if batched:
        return d_all[:, topo.nbrs, topo.rev]             # [B, N, P, U]
    return d_all[topo.nbrs, topo.rev]                    # [N, P, U]


def _fold_slots(stack, join):
    """⊔ (the lattice's ``join``) over the leading slot axis of a state
    pytree (P is small and static)."""
    acc = jax.tree.map(lambda a: a[0], stack)
    for q in range(1, jax.tree.leaves(stack)[0].shape[0]):
        acc = join(acc, jax.tree.map(lambda a: a[q], stack))
    return acc


def fused_receive(algo, x, buf, buf_elems, cpu, d_all, acc_dtype,
                  faults=None, want_recv: bool = False,
                  want_inbox: bool = False):
    """Execute Alg 2 lines 14-17 for all P slots in one kernel pass.

    ``algo`` duck-types SyncAlgorithm (name/flags/lattice/topo). Returns the
    updated ``(x, buf, buf_elems, cpu, recv, inbox)`` with semantics
    bit-identical to the reference per-slot loop; ``recv`` is the telemetry
    ``(recv_elems, novel_elems)`` per-node pair (DESIGN.md §18) summed from
    the kernel's always-emitted ``dsz``/``cnt`` tallies when ``want_recv``,
    else None; ``inbox`` is the active-masked [(B,) N, P, ...U] received
    δ-groups — exactly what the slot-order fold consumed, ⊥ where a slot
    was suppressed — when ``want_inbox`` (provenance replay, DESIGN.md
    §19), else None. The kernel launch itself is unchanged either way:

    * the kernel emits per-(node, slot) novel counts ``cnt`` against the
      RUNNING state, so the reference loop's global reductions reduce to
      scalar tests:  ¬(d ⊑ x) ⇔ cnt > 0  and  Δ(d, x) = ⊥ ⇔ cnt = 0;
    * RR buffers store Δ extractions — already ⊥ wherever not novel, so the
      reference's ``keep`` masking is the identity and slots write through;
    * classic/BP buffers store whole δ-groups gated by the inflation check,
      applied here as a cnt-derived mask on the gathered inbox;
    * fault masks (message loss / churn, DESIGN.md §12) fold with the
      topology padding mask into the kernel's active-slot input — a
      dropped slot contributes nothing to x, counts, or buffers, exactly
      like the reference loop's widened ``valid`` mask;
    * sweep batching (DESIGN.md §13): when ``algo.batch`` is set, the
      state carries a leading config axis ([B, N, U]) and the kernels run
      with a leading batch grid dimension — every config's tiles execute
      the identical per-tile program, so each cell stays bit-identical to
      its unbatched run.
    """
    lat, topo = algo.lattice, algo.topo
    kind = lat.kernel_kind
    p = topo.max_degree
    sax = algo.slot_axis                                 # 1, or 2 batched

    active = topo.mask if faults is None else topo.mask & faults.recv_ok
    if algo.batched and active.shape != x.shape[:-1] + (p,):
        # Lift [N, P] (no faults), [1, N, P] (store-shared schedule,
        # DESIGN.md §15), or any broadcastable shape to the traced config
        # extent (shard-local under shard_map — never algo.batch, which
        # is the global sweep/store width).
        active = jnp.broadcast_to(active, x.shape[:-1] + (p,))
    inbox = gather_inbox(d_all, topo, batched=algo.batched)  # [(B,) N, P, U]
    d_stack = jnp.moveaxis(inbox, sax, 0)                # [P, (B,) N, U]
    x, stored, _, cnt, dsz = kops.round_recv(
        d_stack, x, kind=kind, emit_stored=algo.has_buffer, active=active,
        layout=algo.batch_layout)

    recv = (jnp.sum(dsz, axis=-1, dtype=jnp.int32),
            jnp.sum(cnt, axis=-1, dtype=jnp.int32)) if want_recv else None
    # The kernel masks suppressed slots in VMEM; the provenance replay
    # needs the same masked view on the host side of the launch.
    mib = jnp.where((active != 0)[..., None], inbox,
                    jnp.zeros((), inbox.dtype)) if want_inbox else None
    cpu = cpu + algo._msum(dsz, acc_dtype)
    if not algo.has_buffer:                              # state-based
        return x, buf, buf_elems, cpu, recv, mib

    if algo.extracts:                                    # rr / bprr
        ssz = cnt                                        # |⇓Δ| per (node, slot)
    else:                                                # classic / bp
        keep = cnt > 0                                   # ¬(d ⊑ x_running)
        ssz = dsz * keep

    nbr_slots = (slice(None),) * sax + (slice(None, p),)
    if algo.per_origin:                                  # bp / bprr
        slot_vals = jnp.moveaxis(stored, 0, sax) if algo.extracts \
            else jnp.where(keep[..., None], inbox, jnp.zeros((), inbox.dtype))
        # join (not set): fault retention can leave prior entries in the
        # neighbor slots; after a fault-free clear this is the identity.
        buf = buf.at[nbr_slots].set(lat.join(buf[nbr_slots], slot_vals))
    else:                                                # classic / rr
        add = _fold_slots(stored, lat.join) if algo.extracts \
            else _fold_slots(
                jnp.moveaxis(
                    jnp.where(keep[..., None], inbox,
                              jnp.zeros((), inbox.dtype)),
                    sax, 0),
                lat.join)
        buf = lat.join(buf, add)

    cpu = cpu + algo._msum(ssz, acc_dtype)
    buf_elems = buf_elems + jnp.sum(ssz, axis=-1, dtype=jnp.int32)
    return x, buf, buf_elems, cpu, recv, mib


def mega_round(algo, x, buf, buf_elems, op_delta, acc_dtype, faults=None,
               want_recv: bool = False, want_inbox: bool = False):
    """Execute Algorithm 1/2 phases (1)-(4) of one round through the
    single-launch megakernel (``kernels.round_step``, DESIGN.md §17).

    Returns ``(x, buf, buf_elems, tx, cpu, state_elems, recv, inbox)``
    bit-identical
    to the reference phases: every count the metric arithmetic consumes
    (|⇓δ|, send sizes, received/novel sizes, |⇓x'|) is emitted by the
    kernel as exact int32 per-(node, slot) tallies, and the jnp epilogue
    applies the identical accumulation order; ``recv`` sums the kernel's
    ``dsz``/``cnt`` into the telemetry per-node pair when ``want_recv``
    (DESIGN.md §18), else None. The only per-algorithm work
    left outside the kernel is the classic/bp keep-gated buffer merge,
    whose inflation check ¬(d ⊑ x) reduces over the whole universe (all
    kernel grid tiles) — it consumes the kernel-emitted masked inbox, like
    the fused engine's epilogue. ``want_inbox`` forces the kernel to emit
    that masked inbox even for flavors that don't need it themselves
    (state / rr / bprr) and returns it reshaped to the engine layout
    [(B,) N, P, ...U] for the provenance replay (DESIGN.md §19), else the
    last element is None.
    """
    lat, topo = algo.lattice, algo.topo
    kind = lat.kernel_kind
    p = topo.max_degree
    n = topo.num_nodes
    sax = algo.slot_axis
    batched = algo.batched
    nprefix = 2 if batched else 1
    # States are pytrees: one array, or a lex kind's (t, v) pair of planes
    # that the kernel holds side by side.
    tmap = jax.tree.map
    ushape = jax.tree.leaves(x)[0].shape[nprefix:]

    def flat3(a):                  # [.., N, *U] -> canonical [B, N, u]
        a = a.reshape(a.shape[:nprefix] + (-1,))
        return a if batched else a[None]

    def flat_buf(a):               # [(B,) N, K, *U] -> [K, B, N, u]
        a = a.reshape(a.shape[:sax + 1] + (-1,))
        a = jnp.moveaxis(a, sax, 0)
        return a if batched else a[:, None]

    xv = tmap(flat3, x)
    dv = tmap(flat3, op_delta)
    bdim = jax.tree.leaves(xv)[0].shape[0]
    if algo.has_buffer:
        if algo.per_origin:
            bv = tmap(flat_buf, buf)
        else:                      # flat buffer: K = 1
            bv = tmap(lambda a: flat3(a)[None], buf)
    else:
        bv = None

    # Active mask: topology padding ∧ fault delivery, lifted to the traced
    # config extent (shard-local — never algo.batch; cf. fused_receive).
    active = topo.mask if faults is None else topo.mask & faults.recv_ok
    active = jnp.broadcast_to(active, (bdim, n, p))
    if algo.has_buffer:
        if faults is None:
            dlv_mask = None        # fault-free: unconditional clear
            delivered = jnp.ones((bdim, n), jnp.int32)
        else:
            dlv_mask = jnp.all(faults.send_ok | ~topo.mask, axis=-1) \
                & faults.up
            delivered = jnp.broadcast_to(dlv_mask, (bdim, n))
    else:
        delivered = None

    xo, bo, inbox, dsz_op, xsz, ssend, cnt, dsz = kops.sync_round(
        dv, xv, bv, active, delivered, nbrs=topo.nbrs, rev=topo.rev,
        kind=kind, per_origin=algo.per_origin, extracts=algo.extracts,
        want_inbox=want_inbox, layout=algo.batch_layout)

    def engine_inbox(ib):          # [P, B, N, u] -> [(B,) N, P, ...U]
        def one(a, xl):
            a = jnp.moveaxis(a if batched else a[:, 0], 0, sax)
            return a.reshape(xl.shape[:nprefix] + (p,) + ushape)
        return tmap(one, ib, x)

    mib = engine_inbox(inbox) if want_inbox else None

    def unb(a):
        return a if batched else a[0]

    dsz_op, xsz = unb(dsz_op), unb(xsz)          # [(B,) N]
    ssend, cnt, dsz = unb(ssend), unb(cnt), unb(dsz)  # [(B,) N, P]
    recv = (jnp.sum(dsz, axis=-1, dtype=jnp.int32),
            jnp.sum(cnt, axis=-1, dtype=jnp.int32)) if want_recv else None

    # -- metric arithmetic, in the reference round_step's exact order --------
    with jax.named_scope("round_metrics"):
        # (1) local update
        if algo.has_buffer:
            buf_elems = buf_elems + dsz_op
        cpu = algo._msum(dsz_op, acc_dtype)
        # (2) sends: tx counts what an up sender puts on the wire
        # (DESIGN.md §12)
        send_live = topo.mask if faults is None \
            else topo.mask & faults.up[..., None]
        tx = algo._msum(ssend * send_live, acc_dtype)
        cpu = cpu + tx
        # (3) ack-gated clear (states/buffers cleared in-kernel)
        if algo.has_buffer:
            if faults is None:
                buf_elems = jnp.zeros_like(buf_elems)
            else:
                buf_elems = jnp.where(dlv_mask, 0, buf_elems)
        # (4) receive
        cpu = cpu + algo._msum(dsz, acc_dtype)

    x = tmap(lambda o, xl: unb(o).reshape(xl.shape), xo, x)
    if algo.has_buffer:
        if algo.extracts:                        # rr / bprr: merged in-kernel
            ssz = cnt
        else:                                    # classic / bp: global keep
            keep = cnt > 0                       # ¬(d ⊑ x_running)
            ssz = dsz * keep
        if algo.per_origin:
            b_alg = tmap(lambda o, bl: jnp.moveaxis(
                o if batched else o[:, 0], 0, sax).reshape(bl.shape),
                bo, buf)
        else:
            b_alg = tmap(lambda o, bl: (o[0] if batched else o[0, 0])
                         .reshape(bl.shape), bo, buf)
        if not algo.extracts:
            ib = mib if mib is not None else engine_inbox(inbox)
            keep_u = keep.reshape(keep.shape + (1,) * len(ushape))
            slot_vals = tmap(
                lambda a: jnp.where(keep_u, a, jnp.zeros((), a.dtype)), ib)
            if algo.per_origin:                  # bp
                nbr_slots = (slice(None),) * sax + (slice(None, p),)
                joined = lat.join(tmap(lambda a: a[nbr_slots], b_alg),
                                  slot_vals)
                b_alg = tmap(lambda a, v: a.at[nbr_slots].set(v), b_alg,
                             joined)
            else:                                # classic
                stack = tmap(lambda a: jnp.moveaxis(a, sax, 0), slot_vals)
                b_alg = lat.join(b_alg, _fold_slots(stack, lat.join))
        buf = b_alg
        cpu = cpu + algo._msum(ssz, acc_dtype)
        buf_elems = buf_elems + jnp.sum(ssz, axis=-1, dtype=jnp.int32)

    return x, buf, buf_elems, tx, cpu, xsz, recv, mib


def fused_join_inbox(algo, x, inbox, want_novel: bool = False):
    """Resync receive (DESIGN.md §14): fold all P pre-masked inbox slots
    into x in one ``round_recv`` pass (state tile VMEM-resident across the
    slots; counts/extractions are not needed — the resync modes compute
    sizes and Δ-responses from the shared masked inbox in jnp, so both
    engines consume identical operands by construction). With
    ``want_novel`` the kernel's per-slot novelty tallies are summed into
    the telemetry per-node count and returned as ``(x, novel)``
    (DESIGN.md §18)."""
    d_stack = jnp.moveaxis(inbox, algo.slot_axis, 0)     # [P, (B,) N, U]
    xo, _, _, cnt, _ = kops.round_recv(
        d_stack, x, kind=algo.lattice.kernel_kind, emit_stored=False,
        layout=algo.batch_layout)
    if want_novel:
        return xo, jnp.sum(cnt, axis=-1, dtype=jnp.int32)
    return xo


def fused_digest(x, spec, kind: str, batched: bool = False,
                 layout: str = "grid"):
    """Blockwise digest of the dense state in one ``kernels.digest`` pass;
    bit-identical to ``sync.digest.digest_state`` (shared mixing constants,
    order-independent mod-2^32 arithmetic)."""
    return kops.digest_blocks(x, block_elems=spec.block_elems, kind=kind,
                              batched=batched, layout=layout)


def fused_extract(x, block_masks, spec, batched: bool = False,
                  layout: str = "grid"):
    """Δ(state, block_mask) for all P neighbor slots in one kernel pass
    (the state tile is read once; a jnp composition would stream it from
    HBM P times). Returns [(B,) N, P, U]."""
    return kops.masked_extract(x, block_masks, block_elems=spec.block_elems,
                               batched=batched, layout=layout)


def fused_loo_sends(buf, kind: str, batched: bool = False,
                    layout: str = "grid"):
    """All P leave-one-out sends from the origin-indexed buffer
    [(B,) N, P+1, U] in one ``buffer_fold`` kernel pass (node axis folded
    into the tile space; the config axis of a sweep becomes the kernel's
    leading batch grid dimension, or folds into the tile rows under the
    store engine's ``rows`` layout). Returns [(B,) N, P, U]."""
    orig_dtype = buf.dtype
    if orig_dtype == jnp.bool_:
        buf = buf.astype(kcommon.BOOL_VIEW)              # max ≡ or on {0, 1}
    sax = 2 if batched else 1
    stack = jnp.moveaxis(buf, sax, 0)                    # [P+1, (B,) N, U]
    sends = kops.buffer_fold(stack, kind=kind, batched=batched,
                             layout=layout)              # [P, (B,) N, U]
    return jnp.moveaxis(sends, 0, sax).astype(orig_dtype)
