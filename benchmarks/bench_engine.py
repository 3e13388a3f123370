"""Engine A/B/C benchmark: reference jnp loop vs fused Pallas chain vs the
single-launch megakernel (DESIGN.md §11/§17), across algorithm × universe
size × lattice kind.

Three result classes, kept deliberately separate:

* **Analytic HBM-equivalent element passes** — the roofline quantity the
  kernel engines optimize. Every receive phase is a memory-bound
  elementwise fold, so per-round cost ≈ (passes over the [N, U] state) ×
  (N·U elements). The models below count array traversals (reads + writes
  of universe-sized operands) assuming perfect fusion *inside* each jnp op
  but none across ops — the XLA-vs-Pallas boundary the engines move. The
  megakernel's edge is structural: routing and the P-slot fold never leave
  VMEM, so its pass count is (nearly) degree-independent.

* **Wall-clock on this host** — variance-aware: each (workload, algo,
  engine) cell builds its round step ONCE (``build_round_step`` + one
  ``jax.jit(lax.scan)``), warms up through compilation, then times
  ``REPS ≥ 5`` repetitions under the x64 metric context and reports
  min / median / stdev. min is the comparison statistic (least noise);
  median/stdev are recorded so regressions in variance are visible too.
  Off-TPU the Pallas engines run in interpret mode — the megakernel still
  wins there because a round is ONE emulated launch instead of a
  per-kernel chain, but compiled-backend numbers are the real claim.

* **Tuned tile configs** — each cell stamps the megakernel block
  ``(g, bn)`` the autotuner resolved (kernels.common.tuned_block) and its
  provenance ("default" | "cache" | "tuned"). Run with ``REPRO_AUTOTUNE=1``
  to measure-and-persist winners before the timed section.

Every cell also cross-checks engine equivalence from the *timed* programs
(final states + every stacked metric, exact — zero tolerance), and the
mega/reference wall-clock ratio is gated against
``benchmarks/baselines/engine_smoke.json`` (>10% regression fails) when a
baseline for this backend exists. Emits
``benchmarks/results/BENCH_engine.json``.
"""

from __future__ import annotations

import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BitGSet
from repro.kernels import common as kcommon
from repro.kernels import ops as kops
from repro.obs import TelemetrySpec, annotate
from repro.obs import telemetry as obs_telemetry
from repro.sync import ENGINES, converged, simulator
from repro.sync.algorithms import SyncAlgorithm

from benchmarks import common as C

BASELINE = Path(__file__).resolve().parent / "baselines" / "engine_smoke.json"
WARMUP = 2
REPS = 5
REGRESSION_SLACK = 1.10       # >10% ratio regression vs baseline fails


# -- analytic HBM pass model --------------------------------------------------

def reference_receive_passes(p: int, buffered: bool = True) -> int:
    """[N, U]-array traversals per round, reference engine receive loop.

    Per neighbor slot: gather + ⊥-mask (read d_all slice, write d = 2);
    Δ-extraction / inflation mask (read d, read x, write stored = 3);
    state join (read x, read d, write x = 3); buffer merge (read buf, read
    stored, write buf = 3). State-based sync drops the stored/buffer terms.
    """
    per_slot = 2 + 3 + 3 + (3 if buffered else 0)
    return per_slot * p


def fused_receive_passes(p: int, buffered: bool = True) -> int:
    """Same count for the fused engine: one gather pass over all P slots
    (read P + write P); ONE round_recv kernel pass (read P slots + x, write
    x' + P stored — the state tile never leaves VMEM between slots); buffer
    assembly from the stored stack (read P, write P)."""
    gather = 2 * p
    kernel = (p + 1) + 1 + (p if buffered else 0)
    assembly = 2 * p if buffered else 0
    return gather + kernel + assembly


def mega_receive_passes(p: int, buffered: bool = True,
                        extracts: bool = True) -> int:
    """Megakernel traversals per round: ONE launch reads δ + x + buf and
    writes x' + buf — the sends, the static routing, and the P-slot
    receive fold are VMEM values that never touch HBM, so the RR flavors
    (``extracts``: the Δ-merge resolves in-kernel) are degree-independent.
    The classic/bp keep-gate needs a global reduction, so those flavors
    additionally emit the masked inbox (write P) and run the jnp
    keep-merge epilogue (read P + read/write buf)."""
    kernel = (2 + 1) + (2 if buffered else 0)      # δ,x in; x' out; buf i/o
    if not buffered or extracts:
        return kernel
    return kernel + p + (p + 2)


# -- workloads ----------------------------------------------------------------

def bitgset_workload(nodes: int, events: int):
    bg = BitGSet(universe=nodes * events)

    def op_fn(x, t):
        ids = jnp.arange(nodes) * events + jnp.minimum(t, events - 1)
        m = jnp.zeros((nodes, bg.num_words), jnp.uint32)
        m = m.at[jnp.arange(nodes), ids // 32].set(
            jnp.uint32(1) << (ids % 32).astype(jnp.uint32))
        return bg.add_mask_delta(x, m)

    return bg.lattice, op_fn


def _cells(full: bool):
    nodes = C.NODES
    events = [40, 120] if full else [12, 30]
    for ev in events:
        yield f"gset_u{nodes * ev}", C.gset_workload(nodes, ev), ev
    yield (f"bitgset_u{nodes * (events[-1] * 32)}",
           bitgset_workload(nodes, events[-1] * 32), events[-1])


# -- timing harness -----------------------------------------------------------

def _build_runner(algo: str, lat, topo, op_fn, rounds: int, quiet: int,
                  engine: str, telemetry=None):
    """One jitted scan per cell — compiled once, timed many times. This is
    what ``simulate`` runs internally; re-calling ``simulate`` would pay a
    retrace per repetition and time the tracer, not the program.
    ``telemetry`` builds the instrumented program (DESIGN.md §18) the same
    way ``simulate(telemetry=...)`` does."""
    alg = SyncAlgorithm(name=algo, lattice=lat, topo=topo, engine=engine)
    carry0 = alg.init(None)
    step = simulator.build_round_step(alg, op_fn, rounds, False, False,
                                      telemetry)
    if telemetry is not None:
        carry0 = (obs_telemetry.init_carry(alg), carry0)
    xs = jnp.arange(rounds + quiet)
    run = jax.jit(lambda c0, t: jax.lax.scan(step, c0, t))
    return alg, run, carry0, xs


def _time_reps(run, carry0, xs, reps: int = REPS, warmup: int = WARMUP):
    """Returns (final_out, stats): warm-up through compilation, then
    ``reps`` timed repetitions (block_until_ready) under the x64 metric
    context ``simulate`` uses."""
    with jax.enable_x64(True):
        out = None
        for _ in range(warmup):
            out = jax.block_until_ready(run(carry0, xs))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(run(carry0, xs))
            ts.append(time.perf_counter() - t0)
    stats = {
        "wall_min_s": round(min(ts), 5),
        "wall_median_s": round(statistics.median(ts), 5),
        "wall_stdev_s": round(statistics.stdev(ts), 5) if len(ts) > 1 else 0.0,
        "reps": len(ts),
    }
    return out, stats


def _same_outputs(a, b) -> bool:
    """Exact equality over every leaf of (carry, stacked metrics)."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _tuned_block_for(alg, topo, u: int):
    """Resolve (and, under REPRO_AUTOTUNE=1, measure) the megakernel tile
    for this cell's geometry; returns the stamp dict for the result JSON.

    The bench closure runs the standalone kernel on representative
    operands — the winner lands in the on-disk cache, which the traced
    ``kops.sync_round`` call inside the timed scan then resolves."""
    n, p = topo.num_nodes, topo.max_degree
    kind = alg.lattice.kernel_kind
    k = (p + 1 if alg.per_origin else 1) if alg.has_buffer else 0
    dtype = jnp.uint32 if kind == "bitor" else jnp.int32

    def bench(cfg):
        dv = jnp.ones((1, n, u), dtype)
        xv = jnp.zeros((1, n, u), dtype)
        bv = jnp.zeros((k, 1, n, u), dtype) if k else None
        act = jnp.ones((1, n, p), jnp.int32)
        dlv = jnp.ones((1, n), jnp.int32) if k else None
        out = kops.sync_round(dv, xv, bv, act, dlv, nbrs=topo.nbrs,
                              rev=topo.rev, kind=kind,
                              per_origin=alg.per_origin,
                              extracts=alg.extracts, block=tuple(cfg))
        jax.block_until_ready(out[0])

    block, source = kops.sync_round_block(1, n, u, p=p, k=k, kind=kind,
                                          layout="grid", tune_bench=bench)
    return {"block": list(block), "source": source, "k": k, "kind": kind}


# -- telemetry overhead (DESIGN.md §18) ---------------------------------------

def telemetry_overhead(topo, grid, full: bool = False, verbose: bool = True):
    """Wall-clock cost of the in-scan telemetry channels, and the
    zero-cost claim for the disabled path made testable: with
    ``telemetry=None`` the round step is built by the exact pre-telemetry
    code path — the SAME jitted program the grid above already timed — so
    re-timing it here must land inside timing noise of that grid cell
    (gated in ``validate`` at ``TELEMETRY_OFF_SLACK``×). The enabled run
    is informational: the reference engine pays the novelty Δ+size pass
    per slot plus the N-way divergence fold; on the kernel engines the
    novelty counts are free (the kernels always emit ``cnt``)."""
    events = [40, 120] if full else [12, 30]
    rounds = events[-1]
    lat, op_fn = C.gset_workload(C.NODES, rounds)
    wname = f"gset_u{C.NODES * rounds}"
    out = {}
    for eng in ("reference", "mega"):
        base = next(r["wall_min_s"] for r in grid
                    if r["workload"] == wname and r["algo"] == "bprr"
                    and r["engine"] == eng)
        _, run_off, c0, xs = _build_runner("bprr", lat, topo, op_fn,
                                           rounds, C.QUIET, eng)
        with annotate(f"bench_engine/telemetry_off/{eng}"):
            _, off = _time_reps(run_off, c0, xs)
        _, run_on, c0t, xs = _build_runner("bprr", lat, topo, op_fn,
                                           rounds, C.QUIET, eng,
                                           telemetry=TelemetrySpec())
        with annotate(f"bench_engine/telemetry_on/{eng}"):
            _, on = _time_reps(run_on, c0t, xs)
        out[eng] = {
            "workload": wname, "algo": "bprr",
            "off": off, "on": on,
            "off_over_grid": round(off["wall_min_s"] / base, 3),
            "on_over_off": round(on["wall_min_s"] / off["wall_min_s"], 3),
        }
        if verbose:
            print(f"  telemetry {eng:10s} off={off['wall_min_s']*1e3:8.2f}ms "
                  f"(grid×{out[eng]['off_over_grid']:5.2f})  "
                  f"on={on['wall_min_s']*1e3:8.2f}ms "
                  f"(off×{out[eng]['on_over_off']:5.2f})")
    return out


TELEMETRY_OFF_SLACK = 1.30    # same program, re-timed: noise band only


# -- benchmark ----------------------------------------------------------------

ALGOS = ("classic", "rr", "bprr")


def run(full: bool = False, verbose: bool = True):
    t_start = time.time()
    topo = C.topo_of("mesh", C.NODES)
    p = topo.max_degree
    grid, cells, mismatches = [], [], []
    for wname, (lat, op_fn), rounds in _cells(full):
        for algo in ALGOS:
            outs, stats, tuned = {}, {}, None
            for eng in ENGINES:
                alg, runner, c0, xs = _build_runner(
                    algo, lat, topo, op_fn, rounds, C.QUIET, eng)
                if eng == "mega":
                    u = int(np.prod(jax.tree.leaves(c0.x)[0].shape[1:]))
                    tuned = _tuned_block_for(alg, topo, u)
                outs[eng], stats[eng] = _time_reps(runner, c0, xs)
                metrics = outs[eng][1][0]
                grid.append({
                    "workload": wname, "algo": algo, "engine": eng,
                    "rounds": rounds + C.QUIET,
                    "tx": int(np.asarray(metrics.tx).sum()),
                    **stats[eng],
                })
            ref = outs["reference"]
            same = all(_same_outputs(ref, outs[eng]) for eng in ENGINES)
            same &= bool(converged(lat, ref[0].x))
            if not same:
                mismatches.append(f"{wname}/{algo}")
            r = {e: stats[e]["wall_min_s"] for e in ENGINES}
            cells.append({
                "workload": wname, "algo": algo,
                "tuned_block": tuned,
                "ratios": {
                    "mega_over_reference": round(r["mega"] / r["reference"],
                                                 3),
                    "mega_over_fused": round(r["mega"] / r["fused"], 3),
                    "fused_over_reference": round(r["fused"] / r["reference"],
                                                  3),
                },
            })
            if verbose:
                print(f"  {wname:18s} {algo:8s} "
                      f"ref={r['reference'] * 1e3:8.2f}ms "
                      f"fused={r['fused'] * 1e3:8.2f}ms "
                      f"mega={r['mega'] * 1e3:8.2f}ms "
                      f"mega/ref={r['mega'] / r['reference']:5.2f} "
                      f"block={tuned['block']}({tuned['source'][0]}) "
                      f"identical={same}")

    tele = telemetry_overhead(topo, grid, full=full, verbose=verbose)

    passes = {
        str(deg): {
            "reference": reference_receive_passes(deg),
            "fused": fused_receive_passes(deg),
            "mega_rr": mega_receive_passes(deg, extracts=True),
            "mega_classic": mega_receive_passes(deg, extracts=False),
        }
        for deg in (3, 4, 8)
    }
    if verbose:
        print("  analytic receive passes/round (buffered):")
        for deg, row in passes.items():
            print(f"    P={deg}: reference={row['reference']:3d}  "
                  f"fused={row['fused']:3d}  mega_rr={row['mega_rr']:3d}  "
                  f"mega_classic={row['mega_classic']:3d}")

    out = {
        "topology": topo.name, "max_degree": p,
        "backend": kcommon.backend_key(),
        "autotune_mode": kcommon.autotune_mode(),
        "timing": {"warmup": WARMUP, "reps": REPS, "statistic": "min"},
        "grid": grid,
        "cells": cells,
        "analytic_receive_passes_per_round": passes,
        "equivalence_mismatches": mismatches,
        "telemetry_overhead": tele,
        "regression": _regression(cells),
        "note": ("wall_* are host timings of the prebuilt jitted scan; "
                 "off-TPU the Pallas engines run interpret mode, where the "
                 "megakernel's one-launch-per-round structure still wins. "
                 "The analytic pass model is the TPU roofline quantity."),
    }
    C.save_result("BENCH_engine", out,
                  harness=C.harness_meta(t_start, len(grid)))
    return out


def geomean_ratio(cells, key: str = "mega_over_reference") -> float:
    """Geometric mean of a wall-clock ratio over all cells — the gated
    aggregate. Per-cell ms-scale timings on a shared host swing far more
    than 10% run-to-run; their geomean is stable (the statistic the >10%
    regression gate can hold without flapping)."""
    logs = [np.log(c["ratios"][key]) for c in cells]
    return float(np.exp(np.mean(logs)))


def _regression(cells):
    """Gate the mega/reference geomean ratio against the recorded baseline
    for THIS backend; >REGRESSION_SLACK× the recorded value is a
    violation. No baseline (or another backend's) → informational skip."""
    now = round(geomean_ratio(cells), 3)
    try:
        base = json.loads(BASELINE.read_text())
    except (OSError, ValueError):
        return {"checked": False, "reason": "no baseline file",
                "geomean_mega_over_reference": now, "violations": []}
    if base.get("backend") != kcommon.backend_key():
        return {"checked": False,
                "reason": f"baseline is for backend {base.get('backend')!r}",
                "geomean_mega_over_reference": now, "violations": []}
    rec = base["geomean_mega_over_reference"]
    limit = round(rec * REGRESSION_SLACK, 3)
    violations = []
    if now > limit:
        violations.append({"geomean_mega_over_reference": now,
                           "baseline": rec, "limit": limit})
    return {"checked": True, "baseline_backend": base.get("backend"),
            "geomean_mega_over_reference": now, "baseline_geomean": rec,
            "limit": limit, "violations": violations}


def validate(out):
    passes = out["analytic_receive_passes_per_round"]
    checks = [
        ("all engines bit-identical from the timed programs (all cells)",
         not out["equivalence_mismatches"]),
        (f"telemetry=None is the unmodified program (re-timed within "
         f"{TELEMETRY_OFF_SLACK}x of its grid cell)",
         all(v["off_over_grid"] <= TELEMETRY_OFF_SLACK
             for v in out["telemetry_overhead"].values())),
    ]
    for deg, row in passes.items():
        checks.append((
            f"pass model: mega < fused < reference @ P={deg}",
            row["mega_rr"] < row["fused"] < row["reference"]
            and row["mega_classic"] < row["fused"],
        ))
    families = {}
    for cell in out["cells"]:
        fam = cell["workload"].split("_u")[0]
        ratio = cell["ratios"]["mega_over_reference"]
        families[fam] = min(families.get(fam, float("inf")), ratio)
    best = {k: round(v, 2) for k, v in families.items()}
    checks.append((
        f"mega beats reference wall-clock on >= 1 workload family {best}",
        any(v <= 1.0 for v in families.values()),
    ))
    checks.append((
        "every cell stamps a tuned/default megakernel block config",
        all(c["tuned_block"] is not None for c in out["cells"]),
    ))
    reg = out["regression"]
    checks.append((
        "mega geomean wall-clock ratio within 10% of recorded baseline"
        + (f" ({reg['geomean_mega_over_reference']} <= {reg['limit']})"
           if reg["checked"] else f" (skipped: {reg['reason']})"),
        not reg["violations"],
    ))
    return checks


if __name__ == "__main__":
    for name, ok in validate(run()):
        print(f"[{'PASS' if ok else 'FAIL'}] {name}")
