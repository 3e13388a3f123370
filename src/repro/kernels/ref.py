"""Pure-jnp oracles for every Pallas kernel (correctness references).

These are deliberately naive — multiple passes, materialized masks — and are
what the tests `assert_allclose` each kernel against across shape/dtype
sweeps (exact equality: all kernels are integer/boolean).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def join(a, b, kind: str = "max"):
    if kind == "max":
        return jnp.maximum(a, b)
    if kind == "bitor":
        return jnp.bitwise_or(a, b)
    if kind == "lex":                 # (t, v₁, …): the larger point
        win = _lex_leq(b, a)
        return tuple(jnp.where(win, ca, cb) for ca, cb in zip(a, b))
    raise ValueError(kind)


def _lex_leq(a, b):
    """a ⊑ b in the lex order, deciding from the first plane: a is below
    where the first plane that differs is smaller, or none differs."""
    decided = jnp.zeros(a[0].shape, bool)
    below = jnp.ones(a[0].shape, bool)
    for ca, cb in zip(a, b):
        below = jnp.where(decided, below, ca < cb)
        decided = decided | (ca != cb)
    return below | ~decided


def lex_delta(d, x):
    """Δ(d, x) over lex points: d's slots that are not ⊥ and not ⊑ x's."""
    bottom = jnp.all(jnp.stack([c == 0 for c in d]), axis=0)
    novel = ~_lex_leq(d, x) & ~bottom
    return tuple(jnp.where(novel, c, 0) for c in d), novel


def delta_extract(d, x, kind: str = "max"):
    if kind == "max":
        novel = d > x
        s = jnp.where(novel, d, jnp.zeros_like(d))
        return s, jnp.maximum(x, d), jnp.sum(novel.astype(jnp.int32))
    if kind == "bitor":
        s = jnp.bitwise_and(d, jnp.bitwise_not(x))
        cnt = jnp.sum(jax.lax.population_count(s).astype(jnp.int32))
        return s, jnp.bitwise_or(x, d), cnt
    raise ValueError(kind)


def round_recv(d_stack, x, kind: str = "max", emit_cov: bool = False):
    """Slot-order receive oracle: d_stack [P, B, U], x [B, U] ->
    (x', stored [P, B, U], cnt [B, P], dsz [B, P]), plus a trailing
    per-element delivery tally cov [B, U] int32 when ``emit_cov``
    (per-word bit tally for kind "bitor")."""
    p = d_stack.shape[0]
    stored, cnt, dsz = [], [], []
    cov = jnp.zeros(x.shape, jnp.int32)
    for q in range(p):
        d = d_stack[q]
        if kind == "max":
            novel = d > x
            s = jnp.where(novel, d, jnp.zeros_like(d))
            cnt.append(jnp.sum(novel, axis=-1).astype(jnp.int32))
            dsz.append(jnp.sum(d != 0, axis=-1).astype(jnp.int32))
            cov = cov + (d != 0).astype(jnp.int32)
            x = jnp.maximum(x, d)
        elif kind == "bitor":
            s = jnp.bitwise_and(d, jnp.bitwise_not(x))
            pc = jax.lax.population_count
            cnt.append(jnp.sum(pc(s), axis=-1).astype(jnp.int32))
            dsz.append(jnp.sum(pc(d), axis=-1).astype(jnp.int32))
            cov = cov + pc(d).astype(jnp.int32)
            x = jnp.bitwise_or(x, d)
        else:
            raise ValueError(kind)
        stored.append(s)
    out = (x, jnp.stack(stored, axis=0),
           jnp.stack(cnt, axis=1), jnp.stack(dsz, axis=1))
    return out + (cov,) if emit_cov else out


def digest_blocks(x, be: int, kind: str = "max"):
    """Blockwise digest oracle: delegates to the canonical pure-jnp digest
    (sync/digest.py) — the kernel must reproduce it bitwise."""
    from repro.sync import digest as dg

    return dg.digest_state(x, dg.DigestSpec(block_elems=be), kind)


def masked_extract(x, block_masks, be: int):
    """Masked block extraction oracle: x [..., U] restricted per slot to
    ``block_masks`` [..., P, nB] -> [..., P, U]."""
    from repro.sync import digest as dg

    spec = dg.DigestSpec(block_elems=be)
    em = dg.block_mask_to_elems(block_masks, x.shape[-1], spec)
    return jnp.where(em, x[..., None, :], jnp.zeros((), x.dtype))


def sync_round(delta, x, buf, active, delivered, *, nbrs, rev,
               kind: str = "max", per_origin: bool = False,
               extracts: bool = False, emit_inbox: bool | None = None):
    """Whole-round oracle for the megakernel (kernels/round_step.py), on the
    same canonical operands as ``ops.sync_round``: delta/x [B, N, U], buf
    [K, B, N, U] or None, active [B, N, P], delivered [B, N] (each state
    operand a (t, v₁, …) tuple of such arrays for kind "lex"). Deliberately
    multi-pass: local join → sends (leave-one-out per-origin) → ack-gated
    clear → routed slot-order receive. ``emit_inbox=None`` keeps the
    classic/bp derivation (buffered, non-extracting); True forces the
    stacked active-masked inbox out regardless (provenance replay)."""
    lex = kind == "lex"

    def each(fn, *pts):               # fn over every plane of the points
        return tuple(map(fn, *pts)) if lex else fn(*pts)

    p = nbrs.shape[-1]
    dsz_op = _size(delta, kind)
    x = join(x, delta, kind)
    if buf is not None:
        k = (buf[0] if lex else buf).shape[0]
        self_slot = k - 1 if per_origin else 0
        slot = [each(lambda c: c[o], buf) for o in range(k)]
        slot[self_slot] = join(slot[self_slot], delta, kind)
        if per_origin:
            sends = [_fold([slot[o] for o in range(k) if o != j], kind)
                     for j in range(p)]
        else:
            sends = [slot[0]] * p
    else:
        sends = [x] * p
    ssend = jnp.stack([_size(s, kind) for s in sends], axis=-1)   # [B, N, P]
    if buf is not None:
        gone = (delivered != 0)[:, :, None]
        slot = [each(lambda c: jnp.where(gone, jnp.zeros((), c.dtype), c), s)
                for s in slot]
    inbox, cnts, dszs = [], [], []
    for q in range(p):
        d = each(lambda *cs: jnp.stack(
            [cs[int(rev[i, q])][:, int(nbrs[i, q])]
             for i in range(nbrs.shape[0])], axis=1), *sends)
        on = (active[:, :, q] != 0)[..., None]
        d = each(lambda c: jnp.where(on, c, jnp.zeros((), c.dtype)), d)
        if kind == "max":
            novel = d > x
            s = jnp.where(novel, d, jnp.zeros_like(d))
            cnts.append(jnp.sum(novel, axis=-1).astype(jnp.int32))
        elif kind == "bitor":
            s = jnp.bitwise_and(d, jnp.bitwise_not(x))
            cnts.append(_size(s, kind))
        else:
            s, novel = lex_delta(d, x)
            cnts.append(jnp.sum(novel, axis=-1).astype(jnp.int32))
        dszs.append(_size(d, kind))
        inbox.append(d)
        x = join(x, d, kind)
        if buf is not None and extracts:
            tgt = q if per_origin else 0
            slot[tgt] = join(slot[tgt], s, kind)
    xsz = _size(x, kind)
    if buf is not None:
        buf = each(lambda *cs: jnp.stack(cs, axis=0), *slot)
    emit = (buf is not None and not extracts) if emit_inbox is None \
        else emit_inbox
    return (x, buf,
            each(lambda *cs: jnp.stack(cs, axis=0), *inbox) if emit
            else None,
            dsz_op, xsz, ssend,
            jnp.stack(cnts, axis=-1), jnp.stack(dszs, axis=-1))


def _size(v, kind: str):
    if kind == "max":
        return jnp.sum((v != 0).astype(jnp.int32), axis=-1, dtype=jnp.int32)
    if kind == "lex":
        return jnp.sum(jnp.any(jnp.stack([c != 0 for c in v]), axis=0)
                       .astype(jnp.int32), axis=-1, dtype=jnp.int32)
    return jnp.sum(jax.lax.population_count(v).astype(jnp.int32), axis=-1,
                   dtype=jnp.int32)


def _fold(slots, kind: str):
    acc = slots[0]
    for s in slots[1:]:
        acc = join(acc, s, kind)
    return acc


def buffer_fold(buf, kind: str = "max"):
    """buf [K, ...] -> sends [K-1, ...]: sends[j] = ⊔_{o≠j} buf[o]."""
    k = buf.shape[0]
    outs = []
    for j in range(k - 1):
        acc = None
        for o in range(k):
            if o == j:
                continue
            acc = buf[o] if acc is None else join(acc, buf[o], kind)
        outs.append(acc)
    return jnp.stack(outs, axis=0)
