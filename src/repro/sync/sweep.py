"""One-program sweep engine: batch a whole experiment grid over a leading
config axis (DESIGN.md §13).

The paper's evaluation figures are grids over {algorithm × topology × seed
× fault level}. Running each cell as its own ``lax.scan`` inside a Python
loop retraces, re-jits, and underutilizes the device per cell — the
dominant cost of the fault/transmission studies. This module runs a sweep
of B configurations *sharing one algorithm, lattice, and topology* as ONE
jitted program:

* states gain a leading config axis ([B, N, ...U]), buffers become
  [B, N, P+1, ...U], fault masks stack to [B, T, N, P];
* the scan body is the *same* ``build_round_step`` program ``simulate``
  uses — all per-cell arithmetic is elementwise or reduces over identical
  axes in identical order, and the fused engine's kernels grow a leading
  batch grid dimension — so **every sweep cell is bit-identical (states
  and all metrics) to the corresponding single ``simulate`` call**, on
  both engines (asserted by ``tests/test_sweep.py``);
* metrics come back per-config ([B, T]), with per-config
  ``convergence_round()`` and ``SimResult.cell(b)`` single-run views;
* optionally the config axis shards across devices via ``shard_map``
  (``launch.mesh.shard_sweep_scan``) — configs never communicate, so the
  sweep is embarrassingly parallel.

What cannot batch: the algorithm name (buffer pytrees differ in shape
across algorithms) and the topology/lattice (neighbor tables and universe
sizes differ). A full figure grid loops over those few outer values and
sweeps everything else — e.g. ``benchmarks/fig_fault.py`` runs 5
algorithms × one B=5 fault-scenario sweep instead of 25 separate scans.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.lattice import Lattice
from repro.obs import provenance as prv
from repro.obs import telemetry as obs
from repro.sync.algorithms import SyncAlgorithm
from repro.sync.digest import DigestSpec
from repro.sync.faults import FaultSchedule, FaultViews
from repro.sync.simulator import (
    SimResult,
    build_round_step,
    collect_result,
    run_scan,
)
from repro.sync.topology import Topology


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """The per-config ingredients of one sweep (DESIGN.md §13).

    ``op_fn(x, t) -> delta`` sees the stacked states ([B, N, ...U]) and
    must return stacked deltas — the config axis is where per-cell seeds /
    op rates / workload variants live. ``stack_op`` builds it from a list
    of single-run op_fns when per-cell closures are more natural. With
    ``shard=True`` the op_fn is traced on device-local blocks, so it must
    derive the config extent from ``x`` (e.g. ``x.shape[0]``) rather than
    closing over B — and per-cell *data* (seed tables) must be indexed in
    a way that shards with x, which ``stack_op`` is not; use a natively
    batched op_fn for sharded sweeps.

    ``x0``: optional stacked initial states [B, N, ...U] (None = all-⊥).

    ``faults``: optional per-cell fault schedules, one entry per config
    (None entries = fault-free cell). All schedules must be bound to the
    shared topology; they are compiled once into stacked [T, B, N, P]
    masks riding the scan as plain inputs.
    """

    batch: int
    op_fn: Callable[[Any, jnp.ndarray], Any]
    x0: Any = None
    faults: Optional[Sequence[Optional[FaultSchedule]]] = None

    def __post_init__(self):
        if self.batch < 1:
            raise ValueError(f"batch must be >= 1, got {self.batch}")
        if self.faults is not None and len(self.faults) != self.batch:
            raise ValueError(
                f"faults has {len(self.faults)} entries for batch "
                f"{self.batch} — one schedule (or None) per config")

    @property
    def has_faults(self) -> bool:
        return self.faults is not None and any(
            f is not None for f in self.faults)

    @staticmethod
    def stack_op(op_fns: Sequence[Callable]) -> Callable:
        """Lift B single-run op_fns into one batched op_fn: cell b's delta
        is computed from cell b's states by ``op_fns[b]``. Convenient, but
        traces every cell's op — prefer a natively-batched op_fn when the
        per-cell difference is just data (seeds, rates)."""

        def op_fn(x, t):
            import jax

            cells = [fn(jax.tree.map(lambda a: a[b], x), t)
                     for b, fn in enumerate(op_fns)]
            return jax.tree.map(lambda *ds: jnp.stack(ds, axis=0), *cells)

        return op_fn

    def stacked_views(self, topo: Topology,
                      total_rounds: int) -> Optional[FaultViews]:
        """Compile the per-cell schedules into scan xs: time-major stacked
        masks ``recv_ok/send_ok [T, B, N, P]`` and ``up [T, B, N]``."""
        if not self.has_faults:
            return None
        per_cell = []
        for b, sched in enumerate(self.faults):
            if sched is None:
                sched = FaultSchedule.none(topo, total_rounds)
            elif not sched.same_topology(topo):
                raise ValueError(
                    f"faults[{b}] was built for topology "
                    f"{sched.topo.name!r}, not {topo.name!r}")
            per_cell.append(sched.views(total_rounds))
        stack = [np.stack([np.asarray(getattr(v, f)) for v in per_cell],
                          axis=1)                       # [T, B, ...]
                 for f in ("recv_ok", "send_ok", "up")]
        return FaultViews(*(jnp.asarray(s) for s in stack))


def simulate_sweep(
    algo: str,
    lattice: Lattice,
    topo: Topology,
    spec: SweepSpec,
    active_rounds: int,
    quiet_rounds: int = 0,
    loo: str = "prefix",
    jit: bool = True,
    engine: str = "reference",
    wide_metrics: bool = True,
    track_convergence: Optional[bool] = None,
    shard: bool = False,
    digest: Optional[DigestSpec] = None,
    telemetry: Optional[obs.TelemetrySpec] = None,
    provenance: Optional[prv.ProvenanceSpec] = None,
) -> SimResult:
    """Run ``spec.batch`` configurations of ``algo`` over the shared
    ``topo``/``lattice`` as one jitted scan.

    Mirrors ``simulate``'s semantics cell-for-cell: the returned
    ``SimResult`` carries [B, T] metrics, [B, N, ...U] final states, and
    ``res.cell(b)`` is bit-identical to the single run with cell b's
    op stream / initial state / fault schedule, on either ``engine``.

    ``track_convergence`` defaults on exactly when any cell has a fault
    schedule (matching ``simulate``). ``shard=True`` splits the config
    axis across local devices via ``shard_map`` (no-op on one device;
    requires ``batch`` divisible by the device count).

    ``telemetry`` attaches the in-scan diagnostic channels (DESIGN.md
    §18) as [B, T, N] arrays — ``res.telemetry.cell(b)`` matches the
    single run's channels, and the extra ys shard with the config axis
    under ``shard=True``. ``provenance`` attaches the per-element lineage
    trace the same way (DESIGN.md §19): [B, N, E] matrices and [B, T, N]
    channels, with ``res.provenance.cell(b)`` matching the single run.
    """
    alg = SyncAlgorithm(name=algo, lattice=lattice, topo=topo, loo=loo,
                        engine=engine, batch=spec.batch, digest=digest)
    carry0 = alg.init(spec.x0)
    total = active_rounds + quiet_rounds
    views = spec.stacked_views(topo, total)
    if track_convergence is None:
        track_convergence = views is not None

    step = build_round_step(alg, spec.op_fn, active_rounds, views is not None,
                            track_convergence, telemetry, provenance)
    if views is None:
        xs = jnp.arange(total)
    else:
        xs = (jnp.arange(total), views.recv_ok, views.send_ok, views.up)

    wrap = None
    if shard:
        from repro.launch import mesh as launch_mesh

        def wrap(run):
            return launch_mesh.shard_sweep_scan(run, spec.batch)

    if telemetry is None and provenance is None:
        carry, (metrics, uniform) = run_scan(step, carry0, xs, jit,
                                             wide_metrics, wrap=wrap)
        return collect_result(carry, metrics, uniform, track_convergence,
                              batched=True)
    wrapped = carry0
    if telemetry is not None:
        wrapped = (obs.init_carry(alg), wrapped)
    if provenance is not None:
        wrapped = (prv.init_carry(provenance, alg, carry0.x), wrapped)
    carry, ys = run_scan(step, wrapped, xs, jit, wide_metrics, wrap=wrap)
    prov_carry = channels = prov_channels = None
    if provenance is not None:
        prov_carry, carry = carry
        prov_channels = ys[-1]
    if telemetry is not None:
        _, carry = carry
        channels = ys[2]
    metrics, uniform = ys[0], ys[1]
    return collect_result(carry, metrics, uniform, track_convergence,
                          batched=True, telemetry=telemetry,
                          channels=channels, provenance=provenance,
                          prov_carry=prov_carry, prov_channels=prov_channels,
                          nbrs=topo.nbrs)
