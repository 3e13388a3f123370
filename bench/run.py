#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. A cell of ``BENCHMARK.json`` names a store
configuration (``bench/configs``) and a traffic mix (``bench/traffic``);
the configuration names its deployment module (``bench/deployments``),
which draws the schedule, builds the store and runs the plain reference
(``bench/spec.py`` gives the interface).

Set-up: import, check for the chip (a run without a TPU, or with fewer
chips than the cell asks for, exits 1 and prints no result), draw the
op schedule from ``--seed``, build the store through the program's
public API and make one whole warm-up experiment call, which compiles
every program the window runs. ``setup_s`` is the time from process start
to the end of that call.

``--trace 0``: a closed loop of whole experiment calls
(``repro.sync.simulate_store``), one caller, back to back; a call starts
while less than ``--seconds`` have passed, and every started call
completes and counts. It reports ``object_rounds_per_s`` (objects x rounds
of every call over the time from the window's start to the last call's
return, each call ending in host-side results), ``peak_hbm_gb`` (the
chip's peak bytes in use after the window) and ``setup_s``.

``--trace 1``: one warm experiment call under the JAX profiler; it reports
the per-layer metrics (``bench/layers/<name>.py`` reads each, from the
trace's device ops and their named scopes), the device busy time and
window, and a breakdown of device ops and idle gaps.

After the window, with the program's state freed, the deployment's plain
reference runs over the same schedule and every call of the
window is compared with it (``bench/check.py``). The numbers compared are
printed beside their limits as the last lines of standard error and under
``checks``, the last key of the result line. JAX's persistent compile cache
lives in ``JAX_COMPILATION_CACHE_DIR`` where that is set, else in
``.jax_cache`` at the root of the checkout; ``setup_cache`` on the result
line counts its hits and misses during the warm-up call.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check, program_trace, spec  # noqa: E402
from bench import peaks as peak_table  # noqa: E402
from bench import xplane  # noqa: E402

CALL_ANNOTATION = "bench.call"
KERNEL = "round_step"              # the megakernel's op name prefix
# jax.monitoring events of the entry's host path, and how the breakdown
# names them
HOST_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "host: trace to jaxpr",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "host: lower to MLIR",
    "/jax/core/compile/backend_compile_duration":
        "host: XLA compile or compile-cache load",
}
LOWER_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                "/jax/core/compile/backend_compile_duration")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str):
    log(f"bench: {msg}")
    raise SystemExit(1)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int):
    """The first ``chips`` TPU devices; exits 1 where JAX finds no TPU or
    fewer chips, and raises for a device kind with no published peaks."""
    from repro.launch.device import require_tpu

    devs = require_tpu()
    if len(devs) < chips:
        fail(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    peak_table.peaks(devs[0].device_kind)
    return devs[:chips]


def enable_compile_cache():
    """The program's rule: ``JAX_COMPILATION_CACHE_DIR`` where it is set,
    else ``.jax_cache`` at the root of the checkout. Every program is
    cached, however short its compile."""
    import jax
    from repro.launch.device import enable_compile_cache as enable

    enable(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class CacheCounter:
    """Counts JAX's persistent compile-cache hits and misses while it is
    entered: a set-up that misses compiled a program its checkout had not
    run (every new seed does; see ``setup_s`` in PERF.md)."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
              "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        self.counts = {"hits": 0, "misses": 0}

    def _on_event(self, event, **_):
        if event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)


class Store:
    """The system under test: the cell's store, built by its deployment
    module over the schedule and called through the program's public entry
    ``repro.sync.simulate_store``."""

    def __init__(self, cell: spec.Cell, deployment, schedule):
        self.cell = cell
        self.lattice, self.topo, self.spec = deployment.store(cell.config,
                                                              schedule)

    def __call__(self, trace=None):
        from repro.sync import simulate_store

        c, cell = self.cell.config, self.cell
        return simulate_store(
            c["algorithm"], self.lattice, self.topo, self.spec,
            cell.active_rounds, cell.rounds - cell.active_rounds,
            engine=c["engine"], layout=c["layout"],
            chunk_rounds=c["chunk_rounds"], track_convergence=True,
            trace=trace)


def timed_window(store: Store, seconds: float):
    """Back-to-back calls while less than ``seconds`` have passed. Returns
    ``(results, raised, elapsed_s, call_s)``, ``call_s`` the seconds of each
    call."""
    results, raised, call_s = [], [], []
    t0 = time.perf_counter()
    t_end = t0
    while time.perf_counter() - t0 < seconds:
        t_call = time.perf_counter()
        try:
            results.append(store())
        except Exception as e:              # a failed call ends the window
            raised.append(repr(e))
            break
        finally:
            t_end = time.perf_counter()
            call_s.append(t_end - t_call)
    return results, raised, t_end - t0, call_s


class _WallClock:
    """``time.time`` that remembers its first reading (the origin of a
    ``TraceLog``'s timestamps)."""

    def __init__(self):
        self.t0 = None

    def __call__(self):
        t = time.time()
        if self.t0 is None:
            self.t0 = t
        return t


def traced_call(store: Store):
    """One call under the profiler. Returns ``(result, raised, profile,
    host)``: ``host`` holds the call's host spans on the profiler's clock,
    its seconds of lowering and compiling, and the ``tf_op`` scope of each
    device op (``program_trace.op_scopes``)."""
    import glob
    import tempfile

    import jax
    import jax.monitoring
    from repro.obs.trace import TraceLog

    spans = []

    def on_span(event, start, end, **_):
        spans.append((event, start, end))

    jax.monitoring.register_event_time_span_listener(on_span)
    clock = _WallClock()
    tlog = TraceLog(clock=clock)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    result, raised = None, []
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            w0 = time.time_ns()
            with jax.profiler.TraceAnnotation(CALL_ANNOTATION):
                try:
                    result = store(trace=tlog)
                except Exception as e:
                    raised.append(repr(e))
        finally:
            jax.profiler.stop_trace()
            jax.monitoring.unregister_event_time_span_listener(on_span)
        files = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        profile = xplane.load(files[0])
        scopes = program_trace.op_scopes(files[0])
    ann = xplane.host_events(profile, CALL_ANNOTATION)
    if not ann:
        raise RuntimeError("the trace holds no call annotation")
    lo, hi = ann[0].start_ns, ann[0].end_ns
    off = lo - w0                   # profiler clock minus host wall clock

    def ns(t_s):
        return t_s * 1e9 + off

    host = {
        "window": (lo, hi),
        "lower_s": sum(e - s for n, s, e in spans if n in LOWER_EVENTS),
        "spans": [(HOST_EVENTS[n], ns(s), ns(e)) for n, s, e in spans
                  if n in HOST_EVENTS],
        "instants": [],
        "phases": [],
        "scopes": scopes,
    }
    for ev in tlog.events:
        at = ns(clock.t0 + ev["ts"] / 1e6)
        if ev["name"] == "chunk_boundary":
            host["instants"].append(
                ("host: chunk boundary (offload, next dispatch)", at))
        elif ev["name"] == "store_scan":
            end = ns(clock.t0 + (ev["ts"] + ev["dur"]) / 1e6)
            host["phases"] += [
                ("host: entry before the scan (validate, build carry)", lo,
                 at),
                ("host: chunk loop (dispatch, offload)", at, end),
                ("host: collect results (device_get)", end, hi)]
    return result, raised, profile, host


def layer_metrics(cell: spec.Cell, deployment, device, host: dict,
                  kind: str) -> dict:
    """Each per-layer metric of the cell, read by its own file; a reader
    that finds nothing returns None and the metric is left out.
    ``ctx["scope_s"](*names)`` gives the device seconds of the ops under
    any of the ``jax.named_scope``s ``names``."""
    ctx = {
        "rounds": cell.rounds,
        "device": device,
        "kernel": KERNEL,
        "lower_s": host["lower_s"],
        "round_bytes": deployment.round_bytes(cell.config),
        "peaks": peak_table.peaks(kind),
        "scope_s": lambda *names: program_trace.scope_s(
            device, host["scopes"], names),
    }
    out = {}
    for m in cell.per_layer:
        value = spec.layer_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(device, host: dict) -> dict:
    ops = sorted(device.op_s.items(), key=lambda kv: -kv[1])[:10]
    lo = host["window"][0]
    gaps = sorted(device.idle, key=lambda g: g[0] - g[1])[:10]
    named = []
    for g in gaps:
        label = xplane.attribute(g, host["spans"], host["instants"],
                                 host["phases"])
        named.append([f"{label} at +{(g[0] - lo) / 1e9:.3f} s",
                      (g[1] - g[0]) / 1e9])
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}


def memory_peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else int(stats["peak_bytes_in_use"])


def execute(cell: spec.Cell, seed: int, seconds: float, trace: int,
            devices, t_start: float = T_START) -> tuple:
    """Set up, run the window, check it. Returns ``(result, checks)``:
    the result line's object, and the numbers compared with their
    limits."""
    import jax

    c = cell.config
    dep = spec.deployment(cell)
    schedule = dep.schedule(c, cell.traffic, seed)
    store = Store(cell, dep, schedule)
    objects = store.spec.objects
    with CacheCounter() as cache:
        store()                              # warm-up: compiles the window
    setup_s = time.perf_counter() - t_start
    log(f"bench: {cell.name}: set-up {setup_s:.3f} s, compile cache "
        f"{cache.counts['hits']} hits, {cache.counts['misses']} misses")

    metrics, dev_extra, extra = {}, {}, {}
    if trace:
        result, raised, profile, host = traced_call(store)
        results = [] if result is None else [result]
        device = xplane.device_time(profile, *host["window"])
        if device.planes == 0 or device.busy_s <= 0:
            raise RuntimeError("the trace shows no device operation")
        metrics = layer_metrics(cell, dep, device, host,
                                devices[0].device_kind)
        dev_extra = {"busy_s": device.busy_s, "window_s": device.window_s}
        extra["breakdown"] = breakdown(device, host)
    else:
        results, raised, elapsed, call_s = timed_window(store, seconds)
        log(f"bench: {len(results)} calls in {elapsed:.3f} s, each "
            + " ".join(f"{s:.3f}" for s in call_s))
    peak = memory_peak_bytes(devices[0])
    if not trace:
        e2e = {"object_rounds_per_s":
               len(results) * objects * cell.rounds / elapsed,
               "setup_s": setup_s}
        if peak is not None:
            e2e["peak_hbm_gb"] = peak / 1e9
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    for msg in raised:
        log(f"bench: a call raised {msg}")

    outs = [check.call_outputs(r) for r in results]
    del results, store
    jax.clear_caches()                       # free the program's executables
    ref = dep.reference(c, schedule, cell.rounds)
    numbers, bad_calls = check.compare(outs, ref, dep.leq)
    attempted = len(outs) + len(raised)
    failed = bad_calls + len(raised)
    correct = bool(check.within(numbers) and failed == 0 and attempted > 0)
    result = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": peak, **dev_extra},
        "setup_cache": cache.counts,
        **extra,
    }
    checks = {k: {"value": v, "limit": check.LIMITS[k]}
              for k, v in numbers.items()}
    return result, checks


def main(argv=None):
    args = parse_args(argv)
    cell = spec.cell(args.workload)
    enable_compile_cache()
    devices = require_chips(cell.chips)
    result, checks = execute(cell, args.seed, args.seconds, args.trace,
                             devices)
    for k, v in checks.items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    result["checks"] = checks
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
