"""Reading the program's own marks back from a profiler trace: the ``tf_op``
scope of each device op, the program's host spans, and the idle time they
hold. On hand-made intervals, on the trace recorded before the program
named its scopes (``small.xplane.pb``), and on one recorded after
(``small_scoped.xplane.pb``).

``small_scoped.xplane.pb`` is one warm call of the Retwis store (600
objects, 50 nodes, 4 rounds of which 2 active, 2-round chunks, seed
3000000019) on a TPU v5e under ``jax.profiler``, inside a ``bench.call``
annotation, with ``trace=TraceLog()``.
"""

from pathlib import Path

import pytest

from bench import program_trace as pt
from bench import xplane
from bench.xplane import Event

DATA = Path(__file__).resolve().parent / "data"
OLD = DATA / "small.xplane.pb"
NEW = DATA / "small_scoped.xplane.pb"
ROUNDS = 4


def test_tf_op_of_a_recorded_op():
    scopes = pt.op_scopes(str(OLD))
    assert scopes["fusion.162"] == \
        "jit(run)/while/body/closed_call/convert_element_type:"
    assert "pallas_call" in scopes["round_step_2d.12"]
    # the names are those the device reduction gives the op line's events
    ops = xplane.device_events(xplane.load(str(OLD)))["/device:TPU:0"]
    assert {"fusion.162", "round_step_2d.12"} <= {e.name for e in ops}


def test_in_scope_matches_whole_components():
    tf_op = "jit(run)/while/body/sync/round_metrics/reduce_sum:"
    assert pt.in_scope(tf_op, "sync") and pt.in_scope(tf_op, "round_metrics")
    assert not pt.in_scope(tf_op, "round")
    assert not pt.in_scope("jit(run)/while/body/op_stream_x/add:",
                           "op_stream")


def test_idle_inside_counts_overlap_once():
    spans = [Event("chunk_offload", 10, 20), Event("store_collect", 15, 30)]
    idle = [(0, 12), (18, 25), (40, 50)]
    # covered 10..30: 2 + 7 + 0 ns
    assert pt.idle_inside(idle, spans) == pytest.approx(9e-9)


def test_split_puts_the_first_dispatch_in_the_entry():
    host = [Event("store_validate", 0, 1), Event("store_build", 1, 2),
            Event("chunk_dispatch", 2, 9), Event("chunk_offload", 9, 10),
            Event("chunk_dispatch", 10, 11), Event("chunk_offload", 11, 12),
            Event("store_collect", 12, 14)]
    entry, driver = pt.split(host)
    assert [(e.name, e.start_ns) for e in entry] == [
        ("store_validate", 0), ("store_build", 1), ("chunk_dispatch", 2)]
    assert sorted((e.name, e.start_ns) for e in driver) == [
        ("chunk_dispatch", 10), ("chunk_offload", 9), ("chunk_offload", 11),
        ("store_collect", 12)]
    gaps = pt.label_gaps([(3, 8), (12.5, 13)], host, lo=0)
    assert gaps[0][0].startswith("chunk_dispatch at +0.000")
    assert gaps[1][0].startswith("store_collect")


def test_cache_read_sums_compile_cache_reads(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs,
           jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        def f(x):
            return jnp.sin(x) * 3 + 1

        jax.jit(f)(jnp.arange(7.0)).block_until_ready()     # writes
        jax.clear_caches()
        with pt.CacheRead() as read:
            jax.jit(f)(jnp.arange(7.0)).block_until_ready()  # reads
        assert read.seconds > 0
        after = read.seconds
        jax.clear_caches()
        jax.jit(f)(jnp.arange(7.0)).block_until_ready()      # not counted
        assert read.seconds == after
    finally:
        jax.config.update("jax_compilation_cache_dir", old[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          old[2])
        cc.reset_cache()


@pytest.fixture(scope="module")
def scoped():
    prof = xplane.load(str(NEW))
    (call,) = xplane.host_events(prof, "bench.call")
    return prof, call, pt.op_scopes(str(NEW))


def test_recorded_ops_carry_the_round_steps_scopes(scoped):
    prof, call, scopes = scoped
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    # the megakernel is the op the pallas_call names, inside ``sync``
    (kernel,) = [n for n in dev.op_s if n.startswith("round_step")]
    assert kernel == "round_step.12"
    assert pt.in_scope(scopes[kernel], "sync")
    for scope in ("op_stream", "sync", "round_metrics", "convergence"):
        assert any(pt.in_scope(scopes.get(op, ""), scope)
                   for op in dev.op_s), scope
    # the kernel and the two scope groups hold nine tenths of busy time
    covered = dev.kernel_s("round_step") + pt.scope_s(
        dev, scopes, ["op_stream", "round_metrics", "convergence"])
    assert covered >= 0.9 * dev.busy_s


def test_recorded_host_spans_in_call_order(scoped):
    prof, call, _ = scoped
    names = pt.LEAF_SPANS + ("store_call", "store_scan")
    host = pt.spans(prof, call.start_ns, call.end_ns, names)
    assert [e.name for e in host] == [
        "store_call", "store_validate", "store_build", "store_scan",
        "chunk_dispatch", "chunk_offload", "chunk_dispatch", "chunk_offload",
        "store_collect"]
    inner = host[1:]
    assert all(host[0].start_ns <= e.start_ns and e.end_ns <= host[0].end_ns
               for e in inner)


def test_readings_of_the_recorded_call(scoped):
    prof, call, _ = scoped
    got = pt.readings(str(NEW), ROUNDS)
    assert got["op_stream.ms_per_round"] == pytest.approx(2.3747455)
    assert got["round.metrics_ms_per_round"] == pytest.approx(0.18275225)
    assert got["entry.idle_s_per_call"] == pytest.approx(0.334117859)
    assert got["driver.idle_s_per_call"] == pytest.approx(0.009618663)
    # the entry's and the driver's spans hold all but 5% of the idle time
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    idle = dev.window_s - dev.busy_s
    assert got["entry.idle_s_per_call"] + got["driver.idle_s_per_call"] \
        >= 0.95 * idle
    assert got["idle_gaps"][0][0].startswith("chunk_dispatch at +0.005 s")


def test_layer_readers_of_scopes_on_the_recorded_call(scoped, small_cell):
    """The harness hands the trace's scopes to the layer readers: the two
    readers of scopes give what ``readings`` gives, next to the readers of
    op names."""
    from bench import run, spec

    prof, call, scopes = scoped
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    cell = small_cell("bprr", objects=600, nodes=50, rounds=ROUNDS, active=2)
    host = {"window": (call.start_ns, call.end_ns), "lower_s": 0.0,
            "spans": [], "instants": [], "phases": [], "scopes": scopes}
    got = {k: v["value"] for k, v in
           run.layer_metrics(cell, spec.deployment(cell), dev, host,
                             "TPU v5 lite").items()}
    want = pt.readings(str(NEW), ROUNDS)
    assert set(got) == {m["name"] for m in cell.per_layer}
    for name in ("op_stream.ms_per_round", "round.metrics_ms_per_round"):
        assert got[name] == pytest.approx(want[name], rel=1e-12)
    assert got["op_stream.ms_per_round"] == pytest.approx(2.3747455)
