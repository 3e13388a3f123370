"""The reduction from a profiler trace to device time: on hand-made
intervals, and on a small trace recorded on a TPU v5e (the Retwis store at
600 objects, 4 rounds, one call inside a ``bench.call`` annotation)."""

from pathlib import Path

import pytest

from bench import xplane
from bench.xplane import Event

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_touching():
    ev = [Event("a", 0, 10), Event("b", 5, 12), Event("c", 12, 14),
          Event("d", 20, 25)]
    assert xplane.union(ev) == [(0, 14), (20, 25)]


def test_gaps_and_clip():
    busy = xplane.union(xplane.clip(
        [Event("a", -5, 3), Event("b", 6, 8), Event("c", 9, 30)], 0, 20))
    assert busy == [(0, 3), (6, 8), (9, 20)]
    assert xplane.gaps(busy, 0, 20) == [(3, 6), (8, 9)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]


def test_attribute_prefers_spans_then_instants_then_phases():
    spans = [("lower", 0, 10), ("compile", 8, 30)]
    assert xplane.attribute((5, 25), spans) == "compile"
    assert xplane.attribute((40, 50), spans, [("chunk", 45)]) == "chunk"
    assert xplane.attribute((60, 70), spans, [("chunk", 45)],
                            [("collect", 55, 80)]) == "collect"
    assert xplane.attribute((90, 95), spans) == "host: other"


@pytest.fixture(scope="module")
def recorded():
    prof = xplane.load(str(TRACE))
    ann = xplane.host_events(prof, "bench.call")
    assert len(ann) == 1
    return prof, ann[0]


def test_recorded_trace_device_time(recorded):
    prof, call = recorded
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    assert dev.planes == 1
    assert 0 < dev.busy_s < dev.window_s
    # the scan's while loop holds the round's ops; per-op time leaves it
    # out, so the loop body's ops are not counted twice
    ops = xplane.device_events(prof)["/device:TPU:0"]
    assert any(e.container and e.name.startswith("while") for e in ops)
    assert not any(n.startswith("while") for n in dev.op_s)
    assert sum(dev.op_s.values()) <= dev.busy_s + 1e-9
    # busy plus idle gaps cover the window
    idle = sum(e - s for s, e in dev.idle) / 1e9
    assert idle + dev.busy_s == pytest.approx(dev.window_s, rel=1e-9)
    kernel = dev.kernel_s("round_step")
    assert 0 < kernel < dev.busy_s


def test_recorded_trace_by_hand(recorded):
    """The reduction against a direct walk of the same events."""
    prof, call = recorded
    ops = xplane.device_events(prof)
    (events,) = ops.values()
    inside = [e for e in events
              if e.start_ns >= call.start_ns and e.end_ns <= call.end_ns]
    assert inside[0].name == xplane.op_name(
        "%" + inside[0].name + " = s32[2] add(...)")
    kernel = sum(e.end_ns - e.start_ns for e in inside
                 if e.name.startswith("round_step")) / 1e9
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    assert dev.kernel_s("round_step") == pytest.approx(kernel, rel=1e-12)
    # four rounds, one megakernel launch each
    assert sum(e.name.startswith("round_step") for e in inside) == 4


def test_layer_readers_on_recorded_trace(recorded, small_cell):
    """Every per-layer reader on the recorded call (600 objects, 4 rounds,
    bprr), against the same quantities worked out here. The trace predates
    the program's named scopes, so the readers of scopes find nothing and
    their metrics are left out."""
    from bench import program_trace, roofline, run, spec

    prof, call = recorded
    dev = xplane.device_time(prof, call.start_ns, call.end_ns)
    cell = small_cell("bprr", objects=600, nodes=50, rounds=4, active=2)
    host = {"window": (call.start_ns, call.end_ns), "lower_s": 1.5,
            "spans": [], "instants": [], "phases": [],
            "scopes": program_trace.op_scopes(str(TRACE))}
    got = {k: v["value"] for k, v in
           run.layer_metrics(cell, spec.deployment(cell), dev, host,
                             "TPU v5 lite").items()}
    kernel_round_s = dev.kernel_s("round_step") / 4
    least_s = roofline.round_bytes("bprr", 600, 50, 4, 64) / 819e9
    assert got == pytest.approx({
        "entry.lower_s_per_call": 1.5,
        "device.idle_share": 1 - dev.busy_s / dev.window_s,
        "round_step.ms_per_round": kernel_round_s * 1e3,
        "round_step_roofline": 100 * least_s / kernel_round_s,
        "round.other_ms_per_round": (dev.busy_s / 4 - kernel_round_s) * 1e3,
    }, rel=1e-12)
    assert 0 < got["round_step_roofline"] < 100
    b = run.breakdown(dev, host)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert all(n.startswith("host: other") for n, _ in b["idle_gaps"])
