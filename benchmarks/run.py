"""Benchmark orchestrator — one section per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--smoke]
                                                [--section NAME] [--skip ...]
                                                [--list-sections]

``--section NAME`` runs exactly one section (e.g. CI's
``--section fault --smoke``); ``--skip`` removes sections from the
default full sweep; ``--list-sections`` prints the registry and exits.

Each section prints its table and appends PASS/FAIL validation checks
against the paper's qualitative claims. Every invocation (including
partial ``--section``/``--skip`` runs) merges its outcome into the
repo-root ``BENCH_summary.json`` — one entry per section (check list,
pass/fail, wall clock, run flags) plus environment provenance — so the
latest validation state of the whole registry is readable from one file
without digging through ``benchmarks/results/``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SUMMARY = Path(__file__).resolve().parents[1] / "BENCH_summary.json"


def _checks(checks):
    ok = True
    for name, passed in checks:
        print(f"  [{'PASS' if passed else 'FAIL'}] {name}")
        ok &= bool(passed)
    return ok


def bench_kernels(args):
    import jax.numpy as jnp
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    results = []
    for shape in [(4096, 1024), (1 << 20,)]:
        d = jnp.asarray(rng.integers(0, 100, size=shape), jnp.int32)
        x = jnp.asarray(rng.integers(0, 100, size=shape), jnp.int32)
        s, xj, cnt = ops.delta_extract(d, x)
        rs, rxj, rcnt = ref.delta_extract(d, x)
        ok = bool((s == rs).all() and (xj == rxj).all() and cnt == rcnt)
        results.append((f"delta_extract {shape}", ok))
        print(f"  delta_extract {str(shape):>14} == ref: {ok}")
    buf = jnp.asarray(rng.integers(0, 50, size=(5, 1 << 18)), jnp.int32)
    ok = bool((ops.buffer_fold(buf) == ref.buffer_fold(buf)).all())
    results.append(("buffer_fold", ok))
    print(f"  buffer_fold  (5, 262144) == ref: {ok}")
    dx = jnp.asarray(rng.integers(0, 100, size=(64, 4000)), jnp.int32)
    got = ops.digest_blocks(dx, block_elems=64, kind="max")
    ok = bool((np.asarray(got) == np.asarray(
        ref.digest_blocks(dx, 64, "max"))).all())
    results.append(("digest_blocks", ok))
    print(f"  digest_blocks (64, 4000) == ref: {ok}")
    return results


# -- section registry (name -> title, runner(args) -> checks | None) ----------

def _sec_fig7(args):
    from benchmarks import fig7_transmission as f7
    return f7.validate(f7.run())


def _sec_fig8(args):
    from benchmarks import fig8_gmap as f8
    return f8.validate(f8.run())


def _sec_fig9(args):
    from benchmarks import fig9_metadata as f9
    return f9.validate(f9.run())


def _sec_fig10(args):
    from benchmarks import fig10_memory as f10
    return f10.validate(f10.run())


def _sec_fig11(args):
    from benchmarks import fig11_retwis as f11
    return f11.validate(f11.run(full=args.full))


def _sec_fault(args):
    from benchmarks import fig_fault
    return fig_fault.validate(fig_fault.run(smoke=args.smoke))


def _sec_digest(args):
    from benchmarks import fig_digest
    return fig_digest.validate(fig_digest.run(smoke=args.smoke))


def _sec_sweep(args):
    from benchmarks import bench_sweep
    return bench_sweep.validate(bench_sweep.run(smoke=args.smoke))


def _sec_store(args):
    from benchmarks import bench_store
    return bench_store.validate(
        bench_store.run(smoke=args.smoke, full=args.full))


def _sec_engine(args):
    from benchmarks import bench_engine
    return bench_engine.validate(bench_engine.run(full=args.full))


def _sec_telemetry(args):
    from benchmarks import fig_telemetry
    return fig_telemetry.validate(fig_telemetry.run(smoke=args.smoke))


def _sec_provenance(args):
    from benchmarks import fig_provenance
    return fig_provenance.validate(fig_provenance.run(smoke=args.smoke))


def _sec_roofline(args):
    from benchmarks import roofline_report
    checks = roofline_report.validate_kernel_report(
        roofline_report.kernel_report(full=args.full))
    try:
        roofline_report.table("pod16x16")
    except Exception as e:  # noqa: BLE001
        print(f"  (no dry-run results: {e})")
    return checks


REGISTRY = {
    "fig7": ("Fig 7 — GSet/GCounter transmission (tree, mesh)", _sec_fig7),
    "fig8": ("Fig 8 — GMap K% transmission", _sec_fig8),
    "fig9": ("Fig 9 — synchronization metadata per node", _sec_fig9),
    "fig10": ("Fig 10 — memory ratio vs BP+RR (mesh)", _sec_fig10),
    "fig11": ("Fig 11/12 — Retwis under Zipf contention", _sec_fig11),
    "fault": ("Fault injection — loss/partition/churn (mesh)", _sec_fault),
    "digest": ("Digest resync — joining replica / healed partition "
               "(DESIGN.md §14)", _sec_digest),
    "sweep": ("Sweep engine A/B — one-program batched grid vs per-cell loop",
              _sec_sweep),
    "store": ("Store engine A/B — one-program object store vs per-object "
              "loop (DESIGN.md §15)", _sec_store),
    "engine": ("Engine A/B/C — reference jnp vs fused chain vs megakernel "
               "(DESIGN.md §17)", _sec_engine),
    "telemetry": ("In-scan telemetry — redundancy/staleness channels + "
                  "trace export (DESIGN.md §18)", _sec_telemetry),
    "provenance": ("Delta provenance — per-element waste attribution, "
                   "lineage traces, stall detection (DESIGN.md §19)",
                   _sec_provenance),
    "kernels": ("CRDT Pallas kernels (interpret-mode correctness sweep)",
                bench_kernels),
    "roofline": ("Roofline — per-kernel measured HLO cost vs pass model, "
                 "plus dry-run table", _sec_roofline),
}

SECTIONS = tuple(REGISTRY)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale Retwis (50 nodes / 1500 objects)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized fault/digest/sweep/store sections")
    ap.add_argument("--section", default="", choices=("",) + SECTIONS,
                    help="run exactly one section")
    ap.add_argument("--skip", default="", help="comma list of sections")
    ap.add_argument("--list-sections", action="store_true",
                    help="print the section registry and exit")
    args = ap.parse_args()
    from repro.launch.device import enable_compile_cache

    enable_compile_cache(SUMMARY.parent)
    if args.list_sections:
        for name, (title, _) in REGISTRY.items():
            print(f"  {name:10s} {title}")
        return
    if args.section:
        skip = set(SECTIONS) - {args.section}
    else:
        skip = set(args.skip.split(",")) if args.skip else set()
    unknown = skip - set(SECTIONS)
    if unknown:
        ap.error(f"unknown --skip sections: {sorted(unknown)}")

    t0 = time.time()
    all_ok = True
    sections = {}
    for name, (title, runner) in REGISTRY.items():
        if name in skip:
            continue
        print(f"\n{'=' * 72}\n== {title}\n{'=' * 72}")
        ts = time.time()
        checks = runner(args)
        ok = True
        if checks is not None:
            ok = _checks(checks)
            all_ok &= ok
        # one summary entry per (section, smoke) — a smoke rerun must not
        # clobber the full-scale result, and vice versa
        key = f"{name}@smoke" if args.smoke else name
        sections[key] = {
            "section": name,
            "ok": bool(ok),
            "checks": [[n, bool(p)] for n, p in (checks or [])],
            "wall_s": round(time.time() - ts, 1),
            "ts": _utc_now(),
            "flags": {"full": args.full, "smoke": args.smoke},
        }
    _write_summary(sections)

    print(f"\nbenchmarks done in {time.time()-t0:.0f}s — "
          f"{'ALL CHECKS PASSED' if all_ok else 'SOME CHECKS FAILED'}")
    sys.exit(0 if all_ok else 1)


def _utc_now() -> str:
    import datetime

    return datetime.datetime.now(datetime.timezone.utc) \
        .isoformat(timespec="seconds")


def _write_summary(sections: dict) -> None:
    """Merge this run's section outcomes into the repo-root summary,
    idempotently per (section, smoke) key: rerunning a section replaces
    its own entry in place (timestamped), a smoke run never clobbers the
    full-scale entry of the same section, and untouched sections keep
    their previous result. A stale registry key (renamed/removed section)
    is dropped rather than kept forever."""
    from benchmarks import common as C

    def base(key: str) -> str:
        return key.split("@", 1)[0]

    try:
        doc = json.loads(SUMMARY.read_text())
    except (OSError, ValueError):
        doc = {"sections": {}}
    kept = {k: v for k, v in doc.get("sections", {}).items()
            if base(k) in REGISTRY}
    kept.update(sections)
    order = [k for name in REGISTRY for k in (name, f"{name}@smoke")
             if k in kept]
    doc = {
        "sections": {k: kept[k] for k in order},
        "all_ok": all(s["ok"] for s in kept.values()),
        "sections_run": sorted({base(k) for k in kept}),
        "sections_pending": [k for k in REGISTRY
                             if not any(base(x) == k for x in kept)],
        "env": C.env_meta(),
    }
    SUMMARY.write_text(json.dumps(doc, indent=2) + "\n")
    print(f"\nsummary -> {SUMMARY}")


if __name__ == "__main__":
    main()
