"""Synchronous-round network simulator (paper §V micro-benchmark harness).

Each round, every node (i) executes one update via its δ-mutator, (ii)
synchronizes with all neighbors, exactly like the paper's 1 Hz op+sync tick.
The whole cluster is a single pytree stepped under ``lax.scan`` — the node
axis is just a batch axis of the lattice ops, so a 15-node mesh and a
1000-node fleet run the same jitted program.

``op_fn(x, t) -> delta`` must return the batched δ-mutator output for round
``t`` given current states ``x`` ([N, ...U]); rounds ``t >= active_rounds``
receive no ops (quiescence drain so convergence can be asserted).

Faults (DESIGN.md §12): an optional ``FaultSchedule`` threads per-round
message-loss / partition / churn masks through the scan as plain inputs —
the simulated program stays a single jitted scan, and both engines honor
the masks identically.

Sweeps (DESIGN.md §13): the scan body is built once by
``build_round_step`` and shared between ``simulate`` (one config) and
``sync/sweep.py``'s ``simulate_sweep`` (a leading [B] config axis batching
a whole experiment grid into one program). Keeping one builder is what
makes the sweep invariant checkable: cell b of a sweep runs the *same*
step program as a single ``simulate`` call, just with batched carries.

Metrics are accumulated in int64 (DESIGN.md §10): the scan is traced under
``jax.enable_x64`` so fleet-scale universe × degree × rounds
sums cannot wrap the int32 range. Lattice state dtypes are unaffected (all
states carry explicit dtypes). Set ``wide_metrics=False`` to opt out.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lattice import Lattice
from repro.obs import provenance as prv
from repro.obs import telemetry as obs
from repro.obs.trace import TraceLog, maybe_span
from repro.sync import treeops as T
from repro.sync.algorithms import AlgoCarry, RoundMetrics, SyncAlgorithm
from repro.sync.digest import DigestSpec
from repro.sync.faults import FaultSchedule, RoundFaults
from repro.sync.topology import Topology


class SimResult(NamedTuple):
    tx: np.ndarray           # [T] elements sent per round ([B, T] for sweeps)
    mem: np.ndarray          # [T] elements held (cluster total) per round
    cpu: np.ndarray          # [T] element-ops per round
    max_mem_node: np.ndarray  # [T]
    final_x: Any             # [N, ...U] final states ([B, N, ...U] sweeps)
    uniform: Optional[np.ndarray]  # [T] bool: all nodes identical at round
                                   # end (None when tracking was off)
    telemetry: Any = None    # obs.TelemetryResult when simulate(...,
                             # telemetry=TelemetrySpec()) — DESIGN.md §18
    provenance: Any = None   # obs.ProvenanceResult when simulate(...,
                             # provenance=ProvenanceSpec()) — DESIGN.md §19

    @property
    def batch(self) -> Optional[int]:
        """Config-axis width B for sweep results, None for single runs."""
        return int(self.tx.shape[0]) if self.tx.ndim == 2 else None

    @property
    def total_tx(self) -> int:
        return int(self.tx.sum())

    @property
    def total_cpu(self) -> int:
        return int(self.cpu.sum())

    @property
    def avg_mem(self) -> float:
        return float(self.mem.mean())

    def cell(self, b: int) -> "SimResult":
        """Config b of a sweep result as a single-run SimResult — the view
        the bit-identity invariant (DESIGN.md §13) is stated over."""
        if self.batch is None:
            raise ValueError("not a sweep result (no config axis)")
        return SimResult(
            tx=self.tx[b], mem=self.mem[b], cpu=self.cpu[b],
            max_mem_node=self.max_mem_node[b],
            final_x=jax.tree.map(lambda a: a[b], self.final_x),
            uniform=None if self.uniform is None else self.uniform[b],
            telemetry=None if self.telemetry is None
            else self.telemetry.cell(b),
            provenance=None if self.provenance is None
            else self.provenance.cell(b),
        )

    def convergence_round(self):
        """First round t such that every round ≥ t ended with all nodes
        holding identical states (−1 if never). With quiescence drain this
        is the time-to-convergence measured by the fault benchmark.
        Sweep results get a per-config int array [B]."""
        if self.uniform is None:
            raise ValueError(
                "per-round convergence was not tracked; pass "
                "simulate(track_convergence=True)")
        return first_stable_round(self.uniform)


def first_stable_round(uniform):
    """First round t such that every round ≥ t has ``uniform`` true
    (−1 if never), computed over the trailing (time) axis — shared by
    ``SimResult.convergence_round`` and the store's store-level
    convergence view."""
    uni = np.asarray(uniform, bool)
    stay = np.flip(np.logical_and.accumulate(np.flip(uni, -1), -1), -1)
    out = np.where(uni[..., -1], stay.argmax(-1), -1)
    return int(out) if out.ndim == 0 else out


def cluster_uniform(lattice: Lattice, x, batched: bool = False):
    """All nodes hold the same state: pairwise ⊑ both ways vs node 0.

    The one cluster-agreement test, shared by ``converged()`` and the
    in-scan per-round ``uniform`` tracker (and, batched, by the sweep
    engine). Returns a scalar bool, or [B] with ``batched=True``.
    """
    idx = (slice(None), slice(0, 1)) if batched else (slice(0, 1),)
    xb = jax.tree.map(lambda a: jnp.broadcast_to(a[idx], a.shape), x)
    agree = lattice.leq(x, xb) & lattice.leq(xb, x)      # [(B,) N]
    return jnp.all(agree, axis=-1)


def converged(lattice: Lattice, final_x) -> bool:
    """All nodes hold the same state (pairwise ⊑ both ways vs node 0)."""
    return bool(cluster_uniform(lattice, final_x))


def build_round_step(alg: SyncAlgorithm, op_fn, active_rounds: int,
                     faulty: bool, track_convergence: bool, telemetry=None,
                     provenance=None):
    """Build the pure ``lax.scan`` body for one op+sync round.

    Shared by ``simulate`` (unbatched) and ``simulate_sweep`` (leading
    config axis, selected by ``alg.batch``): the returned ``step`` is the
    per-round program in both cases, which is what keeps every sweep cell
    bit-identical to its single-run equivalent.

    ``faulty``: the scan's xs are ``(t, recv_ok, send_ok, up)`` (a
    ``FaultViews`` rides them) rather than the round index alone; the
    step reads each round's ``RoundFaults`` from the xs tail.

    ``telemetry``: None, or an ``obs.TelemetrySpec`` — the step's carry
    becomes ``(TelemetryCarry, carry)`` and its ys grow a third
    ``TelemetryChannels`` entry (DESIGN.md §18).

    The step's parts carry ``jax.named_scope``s, which every XLA op
    keeps in its metadata (the ``tf_op`` of a profiler trace):
    ``op_stream`` (``op_fn``, its dtype cast and the active-round gate),
    ``sync`` (``alg.round_step``: the megakernel, classic's epilogue and,
    nested, its ``round_metrics``) and ``convergence`` (the ``uniform``
    tracker). Scopes change no value.

    ``provenance``: None, or an ``obs.ProvenanceSpec`` — the carry gains
    an OUTERMOST ``ProvenanceCarry`` (around the telemetry wrap when both
    ride: ``(prov, (tele, carry))``) and the ys a trailing
    ``ProvChannels`` entry (DESIGN.md §19); the algorithms' round runs
    with ``want_inbox=True`` and the per-element replay consumes its
    masked inbox. With both None the step is the exact program it always
    was (the bit-identity invariants of ``tests/test_telemetry.py`` /
    ``tests/test_provenance.py``).
    """
    lattice = alg.lattice

    def step(carry, xs):
        if provenance is not None:
            prov, carry = carry
        if telemetry is not None:
            tele, carry = carry
        if faulty:
            t, rf = xs[0], RoundFaults(*xs[1:])
        else:
            t, rf = xs, None
        x_before = carry.x
        with jax.named_scope("op_stream"):
            delta = op_fn(carry.x, t)
            # Confine wide_metrics' x64 tracing to the metric accumulators:
            # an op_fn with unpinned dtypes would otherwise emit
            # int64/float64 deltas, promote the state, and break the scan
            # carry.
            delta = jax.tree.map(lambda d, xl: d.astype(xl.dtype), delta,
                                 carry.x)
            # The gate stays rank-minimal (scalar, or the fault masks' own
            # rank) and where_bot aligns it per leaf — the closure never
            # bakes in the config extent, so shard_map can run it on local
            # blocks.
            gate = t < active_rounds
            if rf is not None:
                gate = gate & rf.up       # a down node executes no ops
            delta = T.where_bot(gate, delta, lattice.bottom())
        want_recv = telemetry is not None and telemetry.redundancy
        inbox = None
        with jax.named_scope("sync"):
            if want_recv and provenance is not None:
                carry, metrics, recv, inbox = alg.round_step(
                    carry, delta, faults=rf, recv_counts=True,
                    want_inbox=True)
            elif want_recv:
                carry, metrics, recv = alg.round_step(
                    carry, delta, faults=rf, recv_counts=True)
            elif provenance is not None:
                recv = None
                carry, metrics, inbox = alg.round_step(
                    carry, delta, faults=rf, want_inbox=True)
            else:
                recv = None
                carry, metrics = alg.round_step(carry, delta, faults=rf)
        if track_convergence:
            # Per-round cluster agreement (time-to-convergence telemetry).
            with jax.named_scope("convergence"):
                uni = cluster_uniform(lattice, carry.x, batched=alg.batched)
        elif alg.batched:
            lead = jax.tree.leaves(carry.x)[0].shape[0]
            uni = jnp.zeros((lead,), jnp.bool_)
        else:
            uni = jnp.zeros((), jnp.bool_)
        if telemetry is None and provenance is None:
            return carry, (metrics, uni)
        ys = (metrics, uni)
        out = carry
        if telemetry is not None:
            tele, ch = obs.round_channels(telemetry, alg, tele, x_before,
                                          carry, recv, rf)
            ys = ys + (ch,)
            out = (tele, out)
        if provenance is not None:
            prov, pch = prv.round_update(provenance, alg, prov, x_before,
                                         delta, inbox, t)
            ys = ys + (pch,)
            out = (prov, out)
        return out, ys

    return step


def scan_program(step_of: Callable, jit: bool,
                 wrap: Optional[Callable] = None, donate: bool = False):
    """The scan as one program ``run(carry0, xs, operands)``.

    ``step_of(operands)`` builds the ``lax.scan`` body from the program's
    operand arguments (an op stream's tables, DESIGN.md §16); it runs
    while ``run`` traces. ``wrap`` optionally post-processes ``run``
    before jit (``launch.mesh.shard_sweep_scan`` / ``shard_store_scan``
    shard its batch axis and replicate the operands); xs stay an explicit
    argument so wrappers can assign them shardings. ``donate`` hands the
    input carry's buffers to the output carry (``run_scan_chunked``).
    """

    def run(c0, xs_, operands):
        return jax.lax.scan(step_of(operands), c0, xs_)

    if wrap is not None:
        run = wrap(run)
    if jit:
        run = jax.jit(run, donate_argnums=0 if donate else ())
    return run


def metric_context(wide_metrics: bool):
    """The x64 context metrics are traced and run in (DESIGN.md §10)."""
    return jax.enable_x64(True) if wide_metrics else contextlib.nullcontext()


def run_scan(step, carry0, xs, jit: bool, wide_metrics: bool,
             wrap: Optional[Callable] = None):
    """Host wrapper around the jitted scan: jit + the x64 metric context.
    ``wrap`` is ``scan_program``'s."""
    run = scan_program(lambda _: step, jit, wrap)
    with metric_context(wide_metrics):
        return run(carry0, xs, ())


def run_scan_chunked(run, carry0, xs, wide_metrics: bool, chunk: int,
                     operands=(), on_chunk: Optional[Callable] = None,
                     start: int = 0, ys_prefix=None,
                     trace: Optional[TraceLog] = None):
    """Memory-bounded scan driver (DESIGN.md §16): run the scan in time
    chunks of ``chunk`` rounds with the carry DONATED between chunks and
    per-chunk ys (stacked metrics) offloaded to host.

    ``run(carry, xs, operands)`` is the chunk program,
    ``scan_program(..., donate=True)``; ``operands`` are passed to every
    chunk as they are.

    A single ``lax.scan`` over T rounds materializes its stacked ys on
    device — O(batch × T) for a batched store — and XLA cannot reuse the
    input carry's buffers across the program boundary. Chunking bounds
    the device-resident ys to O(batch × chunk), and the donated carry
    hands each chunk's input carry buffers back to XLA for the output
    carry, so peak device memory is O(carry + chunk), independent of T.
    The per-round program is the same ``step`` a monolithic scan would
    run and the carry threads through unchanged, so the result is
    bit-identical to ``run_scan`` (states and all metrics) — asserted by
    ``tests/test_store.py``.

    ``on_chunk(rounds_done, carry, ys_host)`` fires after every chunk
    with the device carry (safe to fetch: the NEXT chunk call is what
    donates it) and the host-stacked ys so far — the store's
    checkpoint hook (DESIGN.md §16). ``start``/``ys_prefix`` resume a
    partially-completed scan: rounds ``[0, start)`` are skipped and
    ``ys_prefix`` (their host ys) is prepended to the output.

    ``trace`` (an ``obs.TraceLog``) records per chunk a ``chunk_dispatch``
    span around the chunk's slice and call (the first one of a program
    not run before holds the trace, lowering and compile or compile-cache
    load; args: ``rounds``), a ``chunk_offload`` span around the ys fetch
    (``bytes``) and a ``chunk_boundary`` instant (``rounds_done``).

    Returns ``(carry, ys)`` with ys as host numpy arrays stacked over
    the full time axis.
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    total = int(jax.tree.leaves(xs)[0].shape[0])
    chunks = [] if ys_prefix is None else [ys_prefix]
    carry = carry0
    with metric_context(wide_metrics):
        for t0 in range(start, total, chunk):
            done = min(t0 + chunk, total)
            with maybe_span(trace, "chunk_dispatch", rounds=done - t0):
                xs_c = jax.tree.map(lambda a: a[t0:t0 + chunk], xs)
                carry, ys = run(carry, xs_c, operands)
            with maybe_span(trace, "chunk_offload") as counts:
                chunks.append(jax.device_get(ys))   # offload to host
                if trace is not None:
                    counts["bytes"] = sum(
                        a.nbytes for a in jax.tree.leaves(chunks[-1]))
            if trace is not None:
                trace.instant("chunk_boundary", rounds_done=done)
            if on_chunk is not None:
                on_chunk(done, carry,
                         _cat_chunks(chunks) if len(chunks) > 1 else
                         chunks[0])
    if not chunks:
        raise ValueError(f"nothing to run: start={start} >= total={total}")
    return carry, _cat_chunks(chunks) if len(chunks) > 1 else chunks[0]


def _cat_chunks(chunks):
    return jax.tree.map(lambda *cs: np.concatenate(cs, axis=0), *chunks)


def collect_result(carry, metrics, uniform, track_convergence: bool,
                   batched: bool = False, telemetry=None, channels=None,
                   provenance=None, prov_carry=None, prov_channels=None,
                   nbrs=None) -> SimResult:
    """Device → host: transpose sweep metrics to [B, T], run the overflow
    check, and assemble the SimResult. ``telemetry``/``channels`` (the
    spec and the scan-stacked ``TelemetryChannels`` ys) attach an
    ``obs.TelemetryResult``, with the same transpose + overflow check
    applied to every channel. ``provenance``/``prov_carry``/
    ``prov_channels``/``nbrs`` (the spec, the final ``ProvenanceCarry``,
    the scan-stacked ``ProvChannels`` ys, and the topology's neighbor
    table) attach an ``obs.ProvenanceResult`` the same way
    (DESIGN.md §19)."""

    def t_major(a):
        a = np.asarray(a)
        return a.swapaxes(0, 1) if batched else a   # scan stacks [T, B]

    tx = t_major(metrics.tx)
    mem = t_major(metrics.mem)
    cpu = t_major(metrics.cpu)
    # Wrap-around in the metric accumulators shows up as negative counts —
    # impossible for element tallies, so fail loudly instead of reporting
    # garbage (can only trigger with wide_metrics=False at extreme scale).
    if (tx < 0).any() or (mem < 0).any() or (cpu < 0).any():
        raise OverflowError(
            "round-metric accumulator overflow: rerun with wide_metrics=True")
    return SimResult(
        tx=tx,
        mem=mem,
        cpu=cpu,
        max_mem_node=t_major(metrics.max_mem_node),
        final_x=jax.device_get(carry.x),
        uniform=t_major(uniform) if track_convergence else None,
        telemetry=None if telemetry is None
        else obs.collect(telemetry, channels, batched),
        provenance=None if provenance is None
        else prv.collect(provenance, jax.device_get(prov_carry),
                         prov_channels, nbrs, batched),
    )


def simulate(
    algo: str,
    lattice: Lattice,
    topo: Topology,
    op_fn: Callable[[Any, jnp.ndarray], Any],
    active_rounds: int,
    quiet_rounds: int = 0,
    x0: Any = None,
    loo: str = "prefix",
    jit: bool = True,
    engine: str = "reference",
    wide_metrics: bool = True,
    faults: Optional[FaultSchedule] = None,
    track_convergence: Optional[bool] = None,
    digest: Optional[DigestSpec] = None,
    telemetry: Optional[obs.TelemetrySpec] = None,
    provenance: Optional[prv.ProvenanceSpec] = None,
) -> SimResult:
    """Run ``active_rounds`` op+sync rounds plus ``quiet_rounds`` sync-only
    drain rounds of ``algo`` over ``topo``.

    ``engine`` selects the sync-round execution path (DESIGN.md §11):
    ``"reference"`` is the pure-jnp per-slot loop, ``"fused"`` the one-pass
    Pallas engine (falls back to reference for lattices without a dense
    kernel kind). Both produce bit-identical results.

    ``faults`` optionally injects message loss / partitions / node churn
    (DESIGN.md §12): the schedule's per-round masks ride the scan as plain
    inputs, so the program stays one jitted scan with no Python branching
    per round; rounds past the schedule run fault-free. Down nodes execute
    no ops. Both engines honor the masks identically, and an all-ok
    schedule is bit-identical to ``faults=None``.

    ``track_convergence`` records per-round cluster agreement
    (``SimResult.uniform`` / ``convergence_round()``) at the cost of two
    extra leq passes per round; default None enables it exactly when a
    fault schedule is given (time-to-convergence is a fault metric).

    ``digest`` overrides the block geometry of the ``digest_driven``
    algorithm (DESIGN.md §14); ignored by every other algorithm.

    ``telemetry`` opts into the in-scan diagnostic channels (DESIGN.md
    §18): pass an ``obs.TelemetrySpec`` and ``SimResult.telemetry`` comes
    back as a per-round, per-node ``obs.TelemetryResult`` (redundancy,
    staleness, buffer occupancy, divergence gap). ``telemetry=None``
    leaves every other result field bit-identical to a run without it.

    ``provenance`` opts into per-element lineage tracing (DESIGN.md §19):
    pass an ``obs.ProvenanceSpec`` and ``SimResult.provenance`` comes back
    as an ``obs.ProvenanceResult`` (birth/source/hop matrices, per-edge
    first deliveries, wasted-transmission attribution by cause). Requires
    a single-dense-array state lattice; composes freely with
    ``telemetry``; ``provenance=None`` is bit-identical to a run without
    it.
    """
    alg = SyncAlgorithm(name=algo, lattice=lattice, topo=topo, loo=loo,
                        engine=engine, digest=digest)
    carry0 = alg.init(x0)
    total = active_rounds + quiet_rounds
    if faults is not None and not faults.same_topology(topo):
        raise ValueError(
            f"FaultSchedule was built for topology {faults.topo.name!r}, "
            f"not {topo.name!r} — its edge masks would land on the wrong "
            "slots")
    views = None if faults is None else faults.views(total)
    if track_convergence is None:
        track_convergence = faults is not None

    step = build_round_step(alg, op_fn, active_rounds, views is not None,
                            track_convergence, telemetry, provenance)
    if views is None:
        xs = jnp.arange(total)
    else:
        xs = (jnp.arange(total), views.recv_ok, views.send_ok, views.up)

    if telemetry is None and provenance is None:
        carry, (metrics, uniform) = run_scan(step, carry0, xs, jit,
                                             wide_metrics)
        return collect_result(carry, metrics, uniform, track_convergence)
    # Wrap order mirrors build_round_step: telemetry inner, provenance
    # outermost.
    wrapped = carry0
    if telemetry is not None:
        wrapped = (obs.init_carry(alg), wrapped)
    if provenance is not None:
        wrapped = (prv.init_carry(provenance, alg, carry0.x), wrapped)
    carry, ys = run_scan(step, wrapped, xs, jit, wide_metrics)
    prov_carry = channels = prov_channels = None
    if provenance is not None:
        prov_carry, carry = carry
        prov_channels = ys[-1]
    if telemetry is not None:
        _, carry = carry
        channels = ys[2]
    metrics, uniform = ys[0], ys[1]
    return collect_result(carry, metrics, uniform, track_convergence,
                          telemetry=telemetry, channels=channels,
                          provenance=provenance, prov_carry=prov_carry,
                          prov_channels=prov_channels, nbrs=topo.nbrs)
