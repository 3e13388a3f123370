"""TraceLog export edge cases (DESIGN.md §18): empty logs, zero-round
simulations, and JSONL↔Chrome equivalence under generated event
sequences. test_telemetry.py covers the happy path; this file pins the
degenerate shapes tooling actually hits (a crashed run exports an empty
trace, a 0-round sweep cell has no counter ticks) and the invariant the
two renderings rely on: they serialize the SAME event list.
"""

import json
import math
import pathlib
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from _hypothesis_compat import given, settings, st
from repro.core import GSet
from repro.core import value_lattices as vl
from repro.core.lattice import MapLattice
from repro.obs import TelemetrySpec, TraceLog
from repro.sync import StoreSpec, simulate, simulate_store, topology
from repro.sync import workloads
from repro.sync.algorithms import SyncAlgorithm
from repro.sync.simulator import build_round_step

N = 4


def _load_both(log, tmp_path):
    chrome, jsonl = tmp_path / "t.json", tmp_path / "t.jsonl"
    log.export_chrome(chrome)
    log.export_jsonl(jsonl)
    doc = json.loads(chrome.read_text())
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    return doc, lines


def test_empty_log_exports(tmp_path):
    """A log with no events must still render valid, loadable documents
    (a run that fails before its first span exports what it has)."""
    doc, lines = _load_both(TraceLog(), tmp_path)
    assert doc["traceEvents"] == [] and doc["displayTimeUnit"] == "ms"
    assert lines == []


def test_zero_round_simulation_exports(tmp_path):
    """total rounds == 0: channels are [0, N], counter rendering emits
    nothing, and the export is still well-formed."""
    lat = GSet(universe=8).lattice

    def no_op(x, t):
        return jnp.zeros_like(x)

    res = simulate("state", lat, topology.ring(N), no_op, 0,
                   quiet_rounds=0, telemetry=TelemetrySpec())
    assert res.telemetry.recv_elems.shape == (0, N)
    log = TraceLog()
    log.add_round_counters(res.telemetry, prefix="zero/")
    assert log.events == []
    doc, lines = _load_both(log, tmp_path)
    assert doc["traceEvents"] == [] and lines == []


def test_span_context_survives_exception(tmp_path):
    """span() closes its complete event even when the body raises — the
    trace of a failed run shows where it died."""
    log = TraceLog()
    with pytest.raises(RuntimeError, match="boom"):
        with log.span("doomed", stage=1):
            raise RuntimeError("boom")
    doc, lines = _load_both(log, tmp_path)
    assert [e["name"] for e in doc["traceEvents"]] == ["doomed"]
    assert doc["traceEvents"] == lines


_EVENTS = st.lists(
    st.tuples(
        st.sampled_from(["instant", "complete", "counter"]),
        st.text(alphabet="abcxyz/:_0", min_size=1, max_size=12),
        st.integers(0, 2**31),
        st.integers(0, 10**6),
    ),
    max_size=40,
)


@settings(max_examples=30, deadline=None)
@given(events=_EVENTS)
def test_jsonl_chrome_round_trip(events):
    """The two exports serialize the SAME event list: reloading the
    Chrome doc's traceEvents and the JSONL lines yields identical objects
    in identical order, for any interleaving of event kinds."""
    log = TraceLog()
    for kind, name, a, b in events:
        if kind == "instant":
            log.instant(name, detail=a)
        elif kind == "complete":
            log.complete(name, float(a), float(b), arg=b)
        else:
            log.counter(name, {"v": a, "w": b})
    with tempfile.TemporaryDirectory() as td:
        doc, lines = _load_both(log, pathlib.Path(td))
    assert doc["traceEvents"] == lines
    assert len(lines) == len(events)
    for (kind, name, a, b), ev in zip(events, lines):
        assert ev["name"] == name
        assert ev["ph"] == {"instant": "i", "complete": "X",
                            "counter": "C"}[kind]
        # reloaded events carry their payload through both renderings
        if kind == "complete":
            assert ev["ts"] == float(a) and ev["dur"] == float(b)
            assert ev["args"]["arg"] == b
        elif kind == "counter":
            assert ev["args"] == {"v": float(a), "w": float(b)}
        else:
            assert ev["args"]["detail"] == a


# -- the store's host spans and device scopes ---------------------------------

STORE_B, STORE_N, STORE_SLOTS, STORE_T, STORE_CHUNK = 4, 6, 8, 5, 2


def _store():
    """A Retwis-shaped store small enough for the CPU: ``STORE_B`` objects
    of ``STORE_SLOTS`` versioned slots on a degree-2 mesh, 3 active and
    2 quiet rounds, weighted so the footprint is computed too."""
    rng = np.random.default_rng(7)
    counts = rng.integers(0, 3, (3, STORE_N, STORE_B)).astype(np.int32)
    lat = MapLattice(STORE_SLOTS, vl.max_int(), "slots").build()
    spec = StoreSpec(objects=STORE_B,
                     op_fn=workloads.versioned_slot_op(counts, STORE_SLOTS),
                     weights=np.arange(1.0, STORE_B + 1))
    return lat, topology.partial_mesh(STORE_N, 2), spec


def _run_store(trace=None):
    lat, topo, spec = _store()
    return simulate_store("bprr", lat, topo, spec, 3, STORE_T - 3,
                          layout="rows",
                          chunk_rounds=STORE_CHUNK, track_convergence=True,
                          trace=trace)


def _spans(log, name=None):
    return [e for e in log.events if e["ph"] == "X"
            and (name is None or e["name"] == name)]


def test_span_records_call_parent_and_counts():
    log = TraceLog()
    with log.span("outer", k=1):
        with log.span("inner") as counts:
            counts["bytes"] = 12
    with log.span("again"):
        pass
    inner, outer, again = _spans(log)
    assert outer["args"] == {"call": 0, "parent": None, "k": 1}
    assert inner["args"] == {"call": 0, "parent": "outer", "bytes": 12}
    assert again["args"]["call"] == 1 and again["args"]["parent"] is None
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_store_spans_form_one_call_tree():
    """A chunked store call records one ``store_call`` whose descendants
    are every other span, one ``chunk_dispatch`` and one ``chunk_offload``
    per chunk, all under the call's id; a second call gets another id."""
    log = TraceLog()
    res = _run_store(log)
    first = list(log.events)
    spans = _spans(log)
    (call,) = _spans(log, "store_call")
    assert call["args"]["parent"] is None
    assert {e["args"]["call"] for e in spans} == {call["args"]["call"]}
    parent = {e["name"]: e["args"]["parent"] for e in spans}
    for name in ("store_validate", "store_build", "store_scan",
                 "store_collect"):
        assert parent[name] == "store_call", name
    assert parent["chunk_dispatch"] == parent["chunk_offload"] == "store_scan"
    chunks = math.ceil(STORE_T / STORE_CHUNK)
    dispatch = _spans(log, "chunk_dispatch")
    offload = _spans(log, "chunk_offload")
    assert len(dispatch) == len(offload) == chunks
    assert [e["args"]["rounds"] for e in dispatch] == [2, 2, 1]
    assert len([e for e in log.events if e["name"] == "chunk_boundary"]) \
        == chunks
    # a chunk's ys: four int64 metrics and the bool uniform flag per
    # (round, object)
    for d, o in zip(dispatch, offload):
        assert o["args"]["bytes"] == d["args"]["rounds"] * STORE_B * (
            4 * 8 + 1)
    (collect,) = _spans(log, "store_collect")
    fetched = sum(np.asarray(a).nbytes
                  for a in jax.tree.leaves(res.sim.final_x))
    assert collect["args"]["bytes"] == fetched + res.final_state_bytes.nbytes
    # spans nest in time as in the tree
    assert all(call["ts"] <= e["ts"] and e["ts"] + e["dur"]
               <= call["ts"] + call["dur"] for e in spans)

    _run_store(log)
    second = [e for e in log.events[len(first):] if e["ph"] == "X"]
    assert {e["args"]["call"] for e in second} == {call["args"]["call"] + 1}


def test_untraced_store_opens_no_annotation(monkeypatch):
    """``trace=None`` creates no profiler annotation; a traced call makes
    one per span and returns bit-identical results."""
    opened = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **kw):
        opened.append(name)
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    plain = _run_store(None)
    assert opened == []
    log = TraceLog()
    traced = _run_store(log)
    assert sorted(opened) == sorted(e["name"] for e in _spans(log))
    for field in ("tx", "mem", "cpu", "max_mem_node", "uniform"):
        a, b = getattr(plain.sim, field), getattr(traced.sim, field)
        assert a.dtype == b.dtype and np.array_equal(a, b), field
    for a, b in zip(jax.tree.leaves(plain.sim.final_x),
                    jax.tree.leaves(traced.sim.final_x)):
        assert np.array_equal(a, b)
    assert np.array_equal(plain.final_state_bytes, traced.final_state_bytes)


@pytest.mark.parametrize("lattice, engine, ran", [
    ("lww", "fused", "reference"), ("lww", "mega", "mega"),
    ("slots", "fused", "fused")])
def test_store_call_records_the_engine_that_ran(monkeypatch, lattice,
                                                engine, ran):
    """``store_call`` records the engine asked for and the one that ran
    after the automatic fallback (LWW maps have no fused kernel), in the
    log and on the profiler annotation."""
    from repro.core import LWWMap

    notes = []
    real = jax.profiler.TraceAnnotation

    def noting(name, **kw):
        notes.append((name, kw))
        return real(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", noting)
    b, n, keys = 2, 5, 16
    if lattice == "lww":
        lat = LWWMap(keys).lattice
        rng = np.random.default_rng(0)
        tables = (rng.integers(0, 2, (2, n, 3)), rng.integers(0, b, (2, n, 3)),
                  rng.integers(0, keys, (2, n, 3)),
                  rng.integers(0, 99, (2, n, 3)))
        spec = StoreSpec(objects=b, op_fn=workloads.lww_write_op(tables,
                                                                 keys))
        topo = topology.partial_mesh(n, 4)
    else:
        lat, topo, spec = _store()
    log = TraceLog()
    simulate_store("bprr", lat, topo, spec, 2, 2, engine=engine, trace=log)
    (call,) = _spans(log, "store_call")
    assert call["args"]["engine"] == engine
    assert call["args"]["engine_ran"] == ran
    (kw,) = [kw for name, kw in notes if name == "store_call"]
    assert kw["engine"] == engine and kw["engine_ran"] == ran


def test_store_spans_label_the_profiler_host_timeline(tmp_path):
    """Under ``jax.profiler`` every span of the log is also an event of
    the same name on the trace's host plane."""
    log = TraceLog()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run_store(log)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [e.name for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]
    for e in _spans(log):
        assert e["name"] in host, e["name"]
    assert host.count("chunk_dispatch") == len(_spans(log, "chunk_dispatch"))


def test_chunk_program_names_its_scopes():
    """The round step's parts carry their named scopes into the lowered
    program, and the megakernel its stable name."""
    lat, topo, spec = _store()
    alg = SyncAlgorithm(name="bprr", lattice=lat, topo=topo, engine="mega",
                        batch=STORE_B, batch_layout="rows")
    step = build_round_step(alg, spec.op_fn, 3, False, True)
    with jax.enable_x64(True):
        text = jax.jit(lambda c, xs: jax.lax.scan(step, c, xs)).lower(
            alg.init(), jnp.arange(STORE_T)).as_text(debug_info=True)
    for scope in ("op_stream", "sync", "sync/round_metrics", "convergence"):
        assert re.search(rf'loc\("{scope}/', text), scope
    assert 'loc("round_step/pallas_call"' in text
