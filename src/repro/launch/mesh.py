"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; only the dry-run / launcher call them after
setting the device count.
"""

from __future__ import annotations

import jax


def _axis_type_kwargs(n: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 256 chips (16 data × 16 model). Multi-pod: 2 × 256."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_type_kwargs(len(axes)))


def make_mesh(shape, axes):
    """Arbitrary mesh (tests use small ones, e.g. (2, 2))."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), **_axis_type_kwargs(len(axes)))


# -- sweep-engine config-axis sharding (DESIGN.md §13) -----------------------

SWEEP_AXIS = "config"


def sweep_mesh(num_devices: int | None = None):
    """1-D mesh over the config axis of a simulation sweep: B independent
    configs are embarrassingly parallel, so each device runs its own block
    of cells with no cross-device collectives."""
    n = len(jax.devices()) if num_devices is None else num_devices
    return jax.make_mesh((n,), (SWEEP_AXIS,), **_axis_type_kwargs(1))


def axis_shards(mesh, axis: str) -> int:
    """Number of shards the named mesh axis splits a batch into (1 when
    the axis is absent — e.g. a 1-D sweep mesh asked about "object")."""
    return int(dict(mesh.shape).get(axis, 1))


def padded_size(batch: int, shards: int) -> int:
    """Smallest multiple of ``shards`` that holds ``batch`` entries —
    the object-axis padding rule (DESIGN.md §16): arbitrary batch sizes
    shard by padding up to the device multiple instead of erroring."""
    return batch + (-batch) % shards


def _shard_axis_scan(run, batch: int, mesh, axis: str, what: str,
                     xs_batched: bool):
    """Shard the leading batch axis of a scan callable across ``mesh``.

    ``run(carry0, xs, *operands)``: every carry/output-carry leaf has the
    batch axis at 0, scan ys (stacked metrics) are time-major with the
    batch axis at 1, ``xs`` is either the round-index array (replicated)
    or a tuple ``(t, *masks)``, and ``operands`` (an op stream's tables)
    replicate. ``xs_batched`` says whether the mask tails carry the
    batch axis at 1 (the sweep's per-cell [T, B, N, P] stacks) or are
    shared by every batch entry and replicate (the store's [T, 1, N, P]
    broadcast views, DESIGN.md §15). Batch entries never communicate, so
    the mapped body needs no collectives — each device just scans its own
    block.

    ``mesh`` may carry more axes than ``axis`` (the 2-D
    ("object", "config") store mesh, DESIGN.md §16): the batch shards
    over ``axis`` only and replicates over the rest.

    Returns ``run`` unchanged when ``axis`` spans a single device
    (nothing to shard).
    """
    ndev = axis_shards(mesh, axis)
    if ndev == 1:
        return run
    if batch % ndev:
        raise ValueError(
            f"{what} {batch} is not divisible by the {ndev}-shard "
            f"{axis!r} mesh axis — pad the batch to "
            f"{padded_size(batch, ndev)} (simulate_store pads "
            f"automatically) or pass a smaller mesh")
    P = jax.sharding.PartitionSpec
    cfg0, cfg1, rep = P(axis), P(None, axis), P()

    def wrapped(carry0, xs, *operands):
        carry_spec = jax.tree.map(lambda _: cfg0, carry0)
        if isinstance(xs, tuple):
            tail = cfg1 if xs_batched else rep
            xs_spec = (rep,) + tuple(tail for _ in xs[1:])
        else:
            xs_spec = rep
        operands_spec = jax.tree.map(lambda _: rep, operands)
        out_carry, out_ys = jax.eval_shape(run, carry0, xs, *operands)
        out_specs = (jax.tree.map(lambda _: cfg0, out_carry),
                     jax.tree.map(lambda _: cfg1, out_ys))
        return jax.shard_map(
            run, mesh=mesh, in_specs=(carry_spec, xs_spec) + operands_spec,
            out_specs=out_specs, check_vma=False)(carry0, xs, *operands)

    return wrapped


def shard_sweep_scan(run, batch: int, mesh=None):
    """Shard the config axis of a sweep scan across devices via
    ``shard_map`` (DESIGN.md §13). Per-cell fault masks shard with their
    cells ([T, B, N, P] at axis 1)."""
    if mesh is None:
        mesh = sweep_mesh()
    return _shard_axis_scan(run, batch, mesh, SWEEP_AXIS, "sweep batch",
                            xs_batched=True)


# -- store-engine object-axis sharding (DESIGN.md §15/§16) --------------------

STORE_AXIS = "object"


def store_mesh(num_devices: int | None = None, config_devices: int = 1):
    """2-D ("object", "config") mesh for the keyed store (DESIGN.md §16).

    Objects are independent CRDTs sharing only the (replicated) topology
    and fault masks, so each device runs its own block of objects with no
    cross-device collectives. ``config_devices`` reserves a second mesh
    axis for config-batched store runs (store sweeps): store carries
    shard over "object" and replicate over "config", so a store scan and
    a config-axis consumer can share one device grid. The default
    ``config_devices=1`` degenerates to pure object sharding over every
    device.
    """
    total = len(jax.devices()) if num_devices is None else num_devices
    if total % config_devices:
        raise ValueError(
            f"{total} devices do not factor into config_devices="
            f"{config_devices} columns")
    shape = (total // config_devices, config_devices)
    return jax.make_mesh(shape, (STORE_AXIS, SWEEP_AXIS),
                         **_axis_type_kwargs(2))


def shard_store_scan(run, objects: int, mesh=None):
    """Shard the object axis of a store scan across devices via
    ``shard_map`` (DESIGN.md §15). Unlike sweeps, the fault-mask xs are
    store-wide [T, 1, N, P] views shared by every object — they replicate
    instead of sharding. With a 2-D ("object", "config") mesh the carries
    shard over "object" and replicate over "config". ``objects`` must be
    a multiple of the object-axis shard count — ``simulate_store`` pads
    arbitrary object counts up to it (``padded_size``) and masks the pad
    back out of the results."""
    if mesh is None:
        mesh = store_mesh()
    return _shard_axis_scan(run, objects, mesh, STORE_AXIS, "store objects",
                            xs_batched=False)
