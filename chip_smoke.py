#!/usr/bin/env python3
"""Chip smoke test: the paper's Retwis store on a TPU, through the public API.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the object-sharded store, four chips

One chip runs the Retwis macro-benchmark of the paper (§V-D, Table II) as a
keyed store: 30,000 objects, each a ``MapLattice(64, max_int)`` of
versioned slots, replicated on the 50 nodes of a degree-4 partial mesh,
synchronized by BP+RR (``bprr``) on the single-launch megakernel
(``engine="mega"``), under the 15/35/50 follow/post/read mix at Zipf 1.0.
Only the number of rounds is cut: 30 active rounds and 20 quiet rounds,
run in chunks of 10. It checks

* the device: a TPU, compiled (not interpreted) kernels, and the engine
  resolving to ``mega``;
* the results: the first 1,500 objects, rerun as their own store on the
  plain jnp ``reference`` engine, match the mega run exactly (final states
  and per-object tx, mem and tx bytes);
* the guarantees: after the quiet rounds every object's 50 replicas hold
  the same state, and every update the op stream issued in the active
  rounds is contained in the state of every replica.

``--chips 4`` runs only the object-sharded store (``shard=True``) on four
chips from this one process and compares it with the same store run
unsharded on one of them; its op stream (``workloads.rotating_slot_op``)
derives the object extent from the state, so it shards.

Lines before the last are observations of this run (device kind, compile
and run seconds, the megakernel tile), not benchmark numbers. The last
line is one JSON object: ``{"ok": true, "device": {...}}``. Any failed
check exits non-zero before it is printed. JAX's persistent compile cache
lives in ``JAX_COMPILATION_CACHE_DIR`` when that is set, else in
``.jax_cache`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

NODES, DEGREE, SLOTS = 50, 4, 64
OBJECTS, CHECK_OBJECTS = 30_000, 1_500
ACTIVE, QUIET, CHUNK = 30, 20, 10
OPS_PER_NODE, ZIPF, SEED = 10, 1.0, 0
ALGO, ENGINE = "bprr", "mega"


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


def check(ok, msg: str):
    if not ok:
        fail(msg)


def log(msg: str):
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX spends lowering to MLIR and compiling with XLA, from
    its own monitoring events (tracing is left out: the trace events of
    nested jits overlap). The rest of a call's wall time is running."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name in self.EVENTS:
            self.seconds += secs


class UpdateWitness:
    """The join, over replicas and active rounds, of every delta the op
    stream issues: ``wrap(op_fn)`` ships each round's per-object join to
    the host. Every such update is acknowledged by the replica that
    applied it, so each replica's final state must contain the join."""

    def __init__(self, objects: int, active: int):
        import numpy as np

        self.active = active
        self.join = np.zeros((objects, SLOTS), np.int64)
        self.rounds = set()

    def _add(self, t, d):
        import numpy as np

        t = int(t)
        if t < self.active:
            self.rounds.add(t)
            np.maximum(self.join, d, out=self.join)

    def wrap(self, op_fn):
        import jax
        import jax.numpy as jnp

        def op(x, t):
            d = op_fn(x, t)
            jax.debug.callback(self._add, t, jnp.max(d, axis=1))
            return d

        return op


def setup():
    """Import the package from this checkout, turn on the compile cache,
    and check the device. Returns ``jax.devices()``."""
    src = ROOT / "src"
    if not (src / "repro" / "sync" / "__init__.py").is_file():
        fail(f"no repro package under {src}: run from a repository checkout")
    if os.environ.get("REPRO_INTERPRET", "").strip().lower() in (
            "1", "true", "yes", "on"):
        fail("REPRO_INTERPRET forces interpret mode; the chip smoke runs "
             "compiled kernels only")
    sys.path.insert(0, str(src))
    from repro.kernels.common import interpret_default
    from repro.launch import device

    device.enable_compile_cache(ROOT)
    try:
        devs = device.require_tpu()
    except SystemExit as e:
        fail(str(e))
    check(not interpret_default(), "kernels would run in interpret mode")
    return devs


def retwis_counts():
    """Per-(round, node, object) update counts [T, N, B] of the Retwis op
    stream over all OBJECTS (the Zipf draw depends on the object count)."""
    from repro.sync import workloads as W

    return W.retwis(objects=OBJECTS, nodes=NODES, rounds=ACTIVE,
                    ops_per_node=OPS_PER_NODE, zipf=ZIPF,
                    seed=SEED).update_counts()


def lattice_and_topology():
    """Versioned-slot objects on a degree-4 partial mesh of NODES."""
    from repro.core import value_lattices as vl
    from repro.core.lattice import MapLattice
    from repro.sync import topology

    return (MapLattice(SLOTS, vl.max_int(), "retwis").build(),
            topology.partial_mesh(NODES, DEGREE))


def retwis_store(counts, witness=None):
    """The smoke's Retwis store over the objects of ``counts``: lattice,
    topology and ``StoreSpec``."""
    from repro.sync import StoreSpec
    from repro.sync import workloads as W

    objects = counts.shape[-1]
    lat, topo = lattice_and_topology()
    op = W.versioned_slot_op(counts, SLOTS)
    if witness is not None:
        op = witness.wrap(op)
    spec = StoreSpec(objects=objects, op_fn=op,
                     weights=W.retwis_weights(objects))
    return lat, topo, spec


def run(lat, topo, spec, engine: str, clock: CompileClock, **kw):
    from repro.sync import simulate_store

    c0, t0 = clock.seconds, time.perf_counter()
    res = simulate_store(ALGO, lat, topo, spec, ACTIVE, QUIET, engine=engine,
                         chunk_rounds=CHUNK, track_convergence=True, **kw)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    return res, compile_s, wall - compile_s


def check_converged(res, what: str):
    import numpy as np

    fx = np.asarray(res.final_x)
    check((fx == fx[:, :1]).all(),
          f"{what}: replicas of some object disagree after {QUIET} quiet "
          "rounds")
    conv = res.store_convergence_round()
    check(0 <= conv < ACTIVE + QUIET,
          f"{what}: store never stayed converged (round {conv})")
    return fx, conv


def check_same(a, b, objects: int, what: str, fields):
    import numpy as np

    check(np.array_equal(np.asarray(a.final_x)[:objects],
                         np.asarray(b.final_x)[:objects]),
          f"{what}: final states differ")
    for f in fields:
        check(np.array_equal(getattr(a, f)[:objects],
                             getattr(b, f)[:objects]),
              f"{what}: per-object {f} differs")


def tile_of(objects: int, lat):
    from repro.kernels import ops as kops

    u = lat.bottom().size
    (g, bn), src = kops.sync_round_block(objects, NODES, u, p=DEGREE,
                                         k=DEGREE + 1, kind=lat.kernel_kind,
                                         layout="rows")
    return g, bn, src


def one_chip(devs):
    import numpy as np
    from repro.sync import engine as engine_mod

    clock = CompileClock()
    witness = UpdateWitness(OBJECTS, ACTIVE)
    counts = retwis_counts()
    lat, topo, spec = retwis_store(counts, witness)
    resolved = engine_mod.resolve(ENGINE, lat)
    check(resolved == ENGINE,
          f"engine {ENGINE!r} resolved to {resolved!r} for {lat.name}")
    g, bn, src = tile_of(OBJECTS, lat)
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    log(f"store: {ALGO} on {resolved}, {OBJECTS} objects x {NODES} replicas "
        f"(partial mesh, degree {DEGREE}) x {SLOTS} slots, Retwis mix, "
        f"zipf {ZIPF}, {OPS_PER_NODE} ops/node/round")
    log(f"cut: rounds only: {ACTIVE} active + {QUIET} quiet, chunks of "
        f"{CHUNK}")
    log(f"megakernel tile (g, bn) = ({g}, {bn}) [{src}]")

    res, comp_s, run_s = run(lat, topo, spec, ENGINE, clock)
    import jax

    jax.effects_barrier()                  # every witness callback landed
    log(f"mega store: compile {comp_s:.3f} s, run {run_s:.3f} s "
        "(observed on this run, not a benchmark)")

    fx, conv = check_converged(res, "mega store")
    log(f"converged: all {OBJECTS} objects by round {conv}")
    check(witness.rounds == set(range(ACTIVE)),
          f"op stream ran for rounds {sorted(witness.rounds)}")
    check(witness.join.any(), "the op stream issued no update")
    check((fx >= witness.join[:, None, :]).all(),
          "an acknowledged update is missing from some replica")
    touched = int((witness.join != 0).any(axis=1).sum())
    log(f"acknowledged updates: all present at every replica "
        f"({touched} objects updated)")

    ref_lat, ref_topo, ref_spec = retwis_store(counts[..., :CHECK_OBJECTS])
    ref, comp_s, run_s = run(ref_lat, ref_topo, ref_spec, "reference", clock)
    log(f"reference store ({CHECK_OBJECTS} objects): compile {comp_s:.3f} s, "
        f"run {run_s:.3f} s")
    check_same(res, ref, CHECK_OBJECTS, "mega vs reference",
               ("tx", "mem", "tx_bytes"))
    check(np.asarray(ref.final_x).any(), "reference store stayed empty")
    log(f"mega == reference on the first {CHECK_OBJECTS} objects: states, "
        "tx, mem, tx_bytes")


def four_chips(devs):
    from repro.sync import StoreSpec
    from repro.sync import workloads as W

    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    clock = CompileClock()
    lat, topo = lattice_and_topology()
    spec = StoreSpec(objects=OBJECTS, op_fn=W.rotating_slot_op(NODES, SLOTS),
                     weights=W.retwis_weights(OBJECTS))
    log(f"device: {devs[0].device_kind} x{len(devs)}")
    log(f"store: {ALGO} on {ENGINE}, {OBJECTS} objects x {NODES} replicas x "
        f"{SLOTS} slots, rotating-slot op stream; {ACTIVE} active + {QUIET} "
        f"quiet rounds, chunks of {CHUNK}")
    one, comp_s, run_s = run(lat, topo, spec, ENGINE, clock)
    log(f"unsharded (1 chip): compile {comp_s:.3f} s, run {run_s:.3f} s")
    check_converged(one, "unsharded store")
    shard, comp_s, run_s = run(lat, topo, spec, ENGINE, clock, shard=True)
    log(f"sharded (4 chips): compile {comp_s:.3f} s, run {run_s:.3f} s")
    check_converged(shard, "sharded store")
    check_same(one, shard, OBJECTS, "sharded vs unsharded",
               ("tx", "mem", "cpu", "tx_bytes"))
    log(f"sharded == unsharded on all {OBJECTS} objects: states, tx, mem, "
        "cpu, tx_bytes")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = setup()
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
