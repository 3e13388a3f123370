"""Roofline reporting (EXPERIMENTS.md §Roofline, §Engine).

Two sections:

* ``kernel_report()`` — per-kernel measured roofline for the sync-round
  engines: each (engine, algo, workload) cell lowers and compiles its ONE
  ROUND program (the exact ``build_round_step`` body the timed scans run),
  feeds the compiled HLO through ``launch.hlo_cost.analyze`` for measured
  FLOPs / HBM bytes, and prices both against the TPU v5e roofline
  constants (``launch.roofline``: 197 TFLOP/s, 819 GB/s — collective term
  0: single-chip kernels). Next to the measured bytes sits the analytic
  pass model (``bench_engine.*_receive_passes``) so the report shows
  measured-vs-modeled HBM traffic per engine. Emits
  ``benchmarks/results/BENCH_roofline.json``.

* ``table()`` — the pre-existing LLM dry-run table: reads
  ``benchmarks/results/dryrun/*.json`` and prints the per-(arch × shape ×
  mesh) three-term breakdown.

Caveat for the kernel section off-TPU: interpret-mode Pallas lowers to an
emulated XLA loop, so measured bytes overstate what compiled Mosaic would
move — the measured/analytic ratio is the honest gap, and rows record the
backend they were compiled for.
"""

from __future__ import annotations

import glob
import json
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parent / "results" / "dryrun"


# -- per-kernel measured roofline (DESIGN.md §17) -----------------------------

def _round_fn(algo, lat, topo, op_fn, engine):
    """The one-round program: carry0 and a step closure over round t=0."""
    import jax.numpy as jnp

    from repro.sync import simulator
    from repro.sync.algorithms import SyncAlgorithm

    alg = SyncAlgorithm(name=algo, lattice=lat, topo=topo, engine=engine)
    carry0 = alg.init(None)
    step = simulator.build_round_step(alg, op_fn, 1, False, False)
    return alg, carry0, step, jnp.int32(0)


def kernel_report(full: bool = False, verbose: bool = True):
    import jax
    import numpy as np

    from repro.launch import roofline as RL
    from repro.launch import hlo_cost
    from repro.sync import ENGINES

    from benchmarks import bench_engine as BE
    from benchmarks import common as C

    t_start = time.time()
    topo = C.topo_of("mesh", C.NODES)
    p = topo.max_degree
    rows = []
    for wname, (lat, op_fn), _rounds in BE._cells(full):
        for algo in BE.ALGOS:
            for eng in ENGINES:
                alg, carry0, step, t0 = _round_fn(algo, lat, topo, op_fn,
                                                  eng)
                with jax.enable_x64(True):
                    jitted = jax.jit(step)
                    compiled = jitted.lower(carry0, t0).compile()
                    out = jax.block_until_ready(jitted(carry0, t0))
                    w0 = time.perf_counter()
                    jax.block_until_ready(jitted(carry0, t0))
                    wall = time.perf_counter() - w0
                cost = hlo_cost.analyze(compiled.as_text(), 1)
                leaf = jax.tree.leaves(carry0.x)[0]
                n, u = leaf.shape[0], int(np.prod(leaf.shape[1:]))
                passes = {
                    "reference": BE.reference_receive_passes(
                        p, alg.has_buffer),
                    "fused": BE.fused_receive_passes(p, alg.has_buffer),
                    "mega": BE.mega_receive_passes(p, alg.has_buffer,
                                                   alg.extracts),
                }[eng]
                analytic_bytes = passes * n * u * leaf.dtype.itemsize
                mem_s = cost.hbm_bytes / RL.HBM_BW
                cmp_s = cost.flops / RL.PEAK_FLOPS
                rows.append({
                    "workload": wname, "algo": algo, "engine": eng,
                    "hlo_flops": cost.flops,
                    "hlo_hbm_bytes": cost.hbm_bytes,
                    "analytic_passes": passes,
                    "analytic_hbm_bytes": analytic_bytes,
                    "measured_over_analytic": round(
                        cost.hbm_bytes / max(analytic_bytes, 1), 2),
                    "roofline_memory_s": mem_s,
                    "roofline_compute_s": cmp_s,
                    "bottleneck": "memory" if mem_s >= cmp_s else "compute",
                    "host_wall_s": round(wall, 5),
                })
                del out
        if verbose:
            for r in rows[-3 * len(ENGINES):]:
                print(f"  {r['workload']:>16s} {r['algo']:8s} "
                      f"{r['engine']:9s} "
                      f"hbm={r['hlo_hbm_bytes'] / 1e6:8.2f}MB "
                      f"(model {r['analytic_hbm_bytes'] / 1e6:6.2f}MB, "
                      f"x{r['measured_over_analytic']:5.1f}) "
                      f"roof={r['roofline_memory_s'] * 1e6:7.1f}us "
                      f"{r['bottleneck'][:3]} "
                      f"wall={r['host_wall_s'] * 1e3:7.2f}ms")

    from repro.kernels import common as kcommon

    out = {
        "topology": topo.name, "max_degree": p,
        "backend": kcommon.backend_key(),
        "constants": {"peak_flops": RL.PEAK_FLOPS, "hbm_bw": RL.HBM_BW},
        "rows": rows,
        "note": ("roofline_* price the compiled one-round HLO at TPU v5e "
                 "constants (collective term 0: single chip). Off-TPU the "
                 "Pallas engines compile interpret-mode emulation, so "
                 "measured_over_analytic >> 1 there is expected; the "
                 "analytic pass model is the deployment-relevant bytes."),
    }
    C.save_result("BENCH_roofline", out,
                  harness=C.harness_meta(t_start, len(rows)))
    return out


def validate_kernel_report(out):
    rows = out["rows"]
    by = {}
    for r in rows:
        by[(r["workload"], r["algo"], r["engine"])] = r
    mega_fewer = all(
        by[(w, a, "mega")]["analytic_hbm_bytes"]
        < by[(w, a, "reference")]["analytic_hbm_bytes"]
        for (w, a, e) in by if e == "mega")
    return [
        ("roofline rows for every (workload, algo, engine) cell",
         len(rows) > 0 and len(rows) % len({r['engine'] for r in rows}) == 0),
        ("measured HLO cost positive for every row",
         all(r["hlo_hbm_bytes"] > 0 for r in rows)),
        ("mega analytic HBM bytes < reference for every cell", mega_fewer),
        ("every row priced (memory/compute roofline terms present)",
         all(r["roofline_memory_s"] > 0 for r in rows)),
    ]


# -- LLM dry-run table (pre-existing) -----------------------------------------

def load(mesh_filter=None):
    rows = []
    for f in sorted(glob.glob(str(RESULTS / "*.json"))):
        d = json.load(open(f))
        if mesh_filter and d.get("mesh") != mesh_filter:
            continue
        rows.append(d)
    return rows


def table(mesh="pod16x16", out=print):
    rows = load(mesh)
    out(f"Roofline — mesh {mesh} (terms in seconds; v5e constants)")
    out(f"{'arch':<20} {'shape':<12} {'GB/dev':>7} {'adjGB':>6} {'fit':>5} "
        f"{'compute':>9} {'memory':>9} {'collect':>9} {'bneck':<10} "
        f"{'useful':>6} {'MFU':>7}")
    n_ok = 0
    for d in rows:
        if d["status"] == "skipped":
            out(f"{d['arch']:<20} {d['shape']:<12} —      skip: {d['reason'][:48]}")
            continue
        if d["status"] == "error":
            out(f"{d['arch']:<20} {d['shape']:<12} ERROR: {d['error'][:60]}")
            continue
        n_ok += 1
        r = d["roofline"]
        m = d["memory"]
        gb = m["peak_gb_per_device"]
        adj = m.get("peak_gb_tpu_adjusted", gb)
        # fit on the bf16-staging-adjusted estimate (EXPERIMENTS §Dry-run)
        fit = "ok" if adj < 16 else "over"
        out(f"{d['arch']:<20} {d['shape']:<12} {gb:7.1f} {adj:6.1f} {fit:>5} "
            f"{r['compute_s']:9.3f} {r['memory_s']:9.3f} "
            f"{r['collective_s']:9.3f} {r['bottleneck']:<10} "
            f"{r['useful_ratio']:6.2f} {r['mfu']:7.4f}")
    out(f"({n_ok} live cells)")
    return rows


def main():
    for name, ok in validate_kernel_report(kernel_report()):
        print(f"[{'PASS' if ok else 'FAIL'}] {name}")
    print()
    table("pod16x16")
    print()
    table("pod2x16x16")


if __name__ == "__main__":
    main()
