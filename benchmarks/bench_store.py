"""Store-engine A/B: one-program keyed store vs the per-object Python
loop (DESIGN.md §15; BENCH_store.json).

The pre-store harness shape — one ``simulate()`` per CRDT object — pays
a fresh trace + compile and thousands of tiny-array dispatches per
object; at store scale (the paper's Retwis runs 30K objects, the ROADMAP
north star is millions) that cost dominates everything. The store engine
runs every object as one jitted scan over [B, N, U] arrays:
one compile, B× larger elementwise ops per dispatch.

The per-object loop is timed on a fixed sample of objects and
extrapolated linearly (per-object trace/compile/dispatch cost is
object-count-independent, which the recorded per-scale sample timings
confirm) — timing *every* object through the loop at 64K objects would
take hours, which is precisely the point being measured. The sampled
objects are checked bit-identical (states + all metrics) to their store
cells before any timing is reported.

Wall-clock here is CPU wall-clock of the *harness*; kernel-level perf
keeps its story in BENCH_engine's analytic pass model.

Two extra sections ride along (DESIGN.md §16):

* **scale curve** — the chunked, metrics-reduced store driven through
  1,000,000 objects on one host. Peak *live device-buffer* bytes are
  probed at every chunk boundary (plus process peak RSS per scale), and
  the per-object byte cost must stay flat as the object count grows
  1000×: the whole point of chunking + in-scan metric reduction is that
  peak memory is O(store + chunk), never O(store × rounds).
* **chunk/resume exercise** — a run is killed right after chunk 1's
  checkpoint lands, resumed from the bundle, and asserted bit-identical
  to the uninterrupted run (the CI smoke gate for the checkpoint path).
"""

from __future__ import annotations

import resource
import tempfile
import time

import jax
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.lattice import MapLattice
from repro.core import value_lattices as vl
from repro.sync import StoreSpec, resume_store, simulate, simulate_store
from repro.sync import workloads as W

from benchmarks import common as C

SCALES = (1024, 4096, 16384)
FULL_SCALES = SCALES + (65536,)
SMOKE_SCALES = (256, 1024)
LOOP_SAMPLE = 16

NODES, SLOTS, ROUNDS, OPS, ZIPF = 16, 32, 20, 4, 1.0
ALGO = "bprr"

# -- scale-curve config: lean per-object footprint so ONE CPU host drives
# a million objects (ring degree 2 bounds the origin buffers at 3 slots)
SCALE_SCALES = (4096, 16384, 65536, 262144, 1048576)
SCALE_SMOKE_SCALES = (2048, 8192)
S_NODES, S_SLOTS, S_ROUNDS, S_CHUNK = 4, 8, 6, 2


def _cells_identical(res, singles_idx, singles):
    for b, single in zip(singles_idx, singles):
        cell = res.object_result(int(b))
        same = (np.array_equal(cell.tx, single.tx)
                and np.array_equal(cell.mem, single.mem)
                and np.array_equal(cell.cpu, single.cpu)
                and np.array_equal(np.asarray(cell.final_x),
                                   np.asarray(single.final_x)))
        if not same:
            return False
    return True


class _LivePeakProbe(Checkpointer):
    """No-op checkpointer that rides the chunk-boundary hook to sample
    peak live device-buffer bytes — nothing touches disk."""

    def __init__(self):                      # no directory on purpose
        self.peak_bytes = 0

    def sample(self):
        n = sum(int(a.nbytes) for a in jax.live_arrays())
        self.peak_bytes = max(self.peak_bytes, n)
        return n

    def save(self, step, state, extra=None):
        self.sample()
        return ""


class _KilledAfterSave(Checkpointer):
    """Real checkpointer that dies right after its first successful save
    — the 'job killed at a chunk boundary' scenario."""

    def save(self, step, state, extra=None):
        out = super().save(step, state, extra)
        raise KeyboardInterrupt("killed after chunk 1 checkpoint")
        return out


def scale_curve(smoke=False, verbose=True):
    """Chunked + metrics-reduced store, 4K → 1M objects: per-object peak
    live-buffer bytes must stay flat (DESIGN.md §16)."""
    scales = SCALE_SMOKE_SCALES if smoke else SCALE_SCALES
    topo = C.topo_of("ring", S_NODES)
    lat = MapLattice(S_SLOTS, vl.max_int(), "scale").build()
    op = W.rotating_slot_op(S_NODES, S_SLOTS)

    rows = []
    for objects in scales:
        spec = StoreSpec(objects=objects, op_fn=op)
        probe = _LivePeakProbe()
        ts = time.time()
        res = simulate_store(ALGO, lat, topo, spec, active_rounds=S_ROUNDS,
                             chunk_rounds=S_CHUNK, checkpoint=probe,
                             object_metrics=False)
        ts = time.time() - ts
        total_tx = int(res.store_tx.sum())
        row = {
            "objects": objects,
            "rounds": S_ROUNDS,
            "chunk_rounds": S_CHUNK,
            "store_s": round(ts, 3),
            "live_peak_mb": round(probe.peak_bytes / 2**20, 1),
            "live_peak_bytes_per_object": round(
                probe.peak_bytes / objects, 1),
            "rss_peak_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**10,
                1),
            "store_total_tx": total_tx,
        }
        rows.append(row)
        if verbose:
            print(f"  scale B={objects:8d}  {ts:7.2f}s  "
                  f"live_peak={row['live_peak_mb']:8.1f}MB  "
                  f"({row['live_peak_bytes_per_object']:7.1f} B/object)  "
                  f"rss={row['rss_peak_mb']:8.1f}MB")
    return rows


def chunk_resume_exercise(verbose=True):
    """Kill a chunked+checkpointed run after chunk 1, resume, compare to
    the uninterrupted run bit for bit."""
    objects = 512
    topo = C.topo_of("ring", S_NODES)
    lat = MapLattice(S_SLOTS, vl.max_int(), "scale").build()
    spec = StoreSpec(objects=objects,
                     op_fn=W.rotating_slot_op(S_NODES, S_SLOTS))

    full = simulate_store(ALGO, lat, topo, spec, active_rounds=S_ROUNDS,
                          chunk_rounds=S_CHUNK)
    with tempfile.TemporaryDirectory() as d:
        try:
            simulate_store(ALGO, lat, topo, spec, active_rounds=S_ROUNDS,
                           chunk_rounds=S_CHUNK,
                           checkpoint=_KilledAfterSave(d))
            killed = False
        except KeyboardInterrupt:
            killed = True
        ck = Checkpointer(d)
        steps = ck.available_steps()
        res = resume_store(ALGO, lat, topo, spec, active_rounds=S_ROUNDS,
                           checkpoint=ck)
        identical = (
            np.array_equal(full.tx, res.tx)
            and np.array_equal(full.mem, res.mem)
            and np.array_equal(full.cpu, res.cpu)
            and np.array_equal(np.asarray(full.final_x),
                               np.asarray(res.final_x)))
    out = {
        "objects": objects,
        "killed_after_chunk_1": bool(killed and steps == [S_CHUNK]),
        "resumed_from_round": S_CHUNK,
        "resume_bit_identical": bool(identical),
    }
    if verbose:
        print(f"  chunk/resume: killed_after_chunk_1="
              f"{out['killed_after_chunk_1']}  "
              f"bit_identical={identical}")
    return out


def run(smoke=False, full=False, verbose=True):
    t0 = time.time()
    scales = SMOKE_SCALES if smoke else (FULL_SCALES if full else SCALES)
    topo = C.topo_of("mesh", NODES)
    lat = MapLattice(SLOTS, vl.max_int(), "retwis").build()

    per_scale = []
    identical = True
    for objects in scales:
        wl = W.retwis(objects, NODES, ROUNDS, OPS, ZIPF, seed=0)
        counts = wl.update_counts()                       # [T, N, B]
        spec = StoreSpec(objects=objects,
                         op_fn=W.versioned_slot_op(counts, SLOTS),
                         weights=W.retwis_weights(objects))

        # -- one-program store (compile + run: compile IS harness cost) -----
        ts = time.time()
        res = simulate_store(ALGO, lat, topo, spec, active_rounds=ROUNDS)
        ts = time.time() - ts

        # -- per-object loop, sampled + extrapolated ------------------------
        sample = min(LOOP_SAMPLE, objects)
        idx = np.linspace(0, objects - 1, sample).astype(int)
        tl = time.time()
        # Keep the SimResults: simulate() already materializes them, so
        # retention is timing-neutral and spares a second identical run
        # for the bit-identity check below.
        singles = [
            simulate(ALGO, lat, topo,
                     W.versioned_slot_cell_op(counts, int(b), SLOTS),
                     active_rounds=ROUNDS)
            for b in idx
        ]
        tl = time.time() - tl
        loop_est = tl / sample * objects

        same = _cells_identical(res, idx, singles)
        identical &= same
        row = {
            "objects": objects,
            "store_s": round(ts, 3),
            "loop_sample_objects": int(sample),
            "loop_sample_s": round(tl, 3),
            "loop_s_per_object": round(tl / sample, 4),
            "loop_s_extrapolated": round(loop_est, 1),
            "speedup_vs_loop": round(loop_est / max(ts, 1e-9), 1),
            "sampled_cells_identical": bool(same),
        }
        per_scale.append(row)
        if verbose:
            print(f"  B={objects:6d}  store={ts:7.2f}s  "
                  f"loop≈{loop_est:9.1f}s "
                  f"({tl:.2f}s/{sample} objects)  "
                  f"speedup={row['speedup_vs_loop']:8.1f}x  "
                  f"identical={same}")

    if verbose:
        print("  -- scale curve (chunked + reduced metrics) --")
    curve = scale_curve(smoke=smoke, verbose=verbose)
    resume = chunk_resume_exercise(verbose=verbose)

    out = {
        "workload": {"algo": ALGO, "topology": topo.name, "nodes": NODES,
                     "slots": SLOTS, "rounds": ROUNDS, "ops_per_node": OPS,
                     "zipf": ZIPF, "engine": "reference"},
        "scale_workload": {"algo": ALGO, "topology": f"ring{S_NODES}",
                           "nodes": S_NODES, "slots": S_SLOTS,
                           "rounds": S_ROUNDS, "chunk_rounds": S_CHUNK,
                           "object_metrics": False},
        "smoke": smoke,
        "scales": per_scale,
        "scale_curve": curve,
        "chunk_resume": resume,
        "cells_identical": bool(identical),
    }
    cells = (sum(r["objects"] + r["loop_sample_objects"] for r in per_scale)
             + sum(r["objects"] for r in curve))
    C.save_result("BENCH_store_smoke" if smoke else "BENCH_store", out,
                  harness=C.harness_meta(t0, cells))
    return out


def validate(out):
    floor_at = 1024 if out["smoke"] else 4096
    floor = 1.5 if out["smoke"] else 3.0
    big = [r for r in out["scales"] if r["objects"] >= floor_at]
    return [
        ("every sampled store cell bit-identical to its per-object run",
         out["cells_identical"]),
        (f"one-program store ≥ {floor}× faster than the per-object loop "
         f"at ≥ {floor_at} objects",
         bool(big) and all(r["speedup_vs_loop"] >= floor for r in big)),
        ("store advantage grows with object count",
         len(out["scales"]) < 2
         or out["scales"][-1]["speedup_vs_loop"]
         >= out["scales"][0]["speedup_vs_loop"]),
        (f"per-object peak live-buffer bytes stay flat over the "
         f"{out['scale_curve'][0]['objects']}→"
         f"{out['scale_curve'][-1]['objects']} object scale curve",
         out["scale_curve"][-1]["live_peak_bytes_per_object"]
         <= out["scale_curve"][0]["live_peak_bytes_per_object"] * 1.25),
        ("chunked run killed after chunk 1 resumes bit-identically",
         out["chunk_resume"]["killed_after_chunk_1"]
         and out["chunk_resume"]["resume_bit_identical"]),
    ]


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    for name, ok in validate(run(smoke=args.smoke, full=args.full)):
        print(f"[{'PASS' if ok else 'FAIL'}] {name}")
