"""What the program writes into a profiler trace, read back by name.

Two kinds of marks, both from ``src/``:

* device scopes: ``jax.named_scope``s of the round step (``op_stream``,
  ``sync``, ``round_metrics``, ``convergence``) that XLA keeps in the
  ``tf_op`` stat of each op's metadata, e.g.
  ``jit(run)/while/body/op_stream/select_n``. ``jax.profiler.ProfileData``
  does not expose metadata stats, so ``op_scopes`` decodes the few
  messages of the ``.xplane.pb`` it needs (the XSpace proto of
  ``tsl/profiler/protobuf/xplane.proto``) with no protobuf library;
* host spans: each span of the program's ``obs.TraceLog`` is a
  ``TraceAnnotation`` of its name on the trace's ``/host:`` plane, on
  the clock of the device events (``spans``).

``readings`` reduces one traced call to the per-layer numbers these marks
give; ``CacheRead`` sums JAX's compile-cache read time while entered.
"""

from __future__ import annotations

from bench import xplane

TF_OP = "tf_op"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# host spans of one simulate_store call, by the device idle they hold
ENTRY_SPANS = ("store_validate", "store_build")     # + the first dispatch
DRIVER_SPANS = ("chunk_offload", "store_collect")   # + later dispatches
DISPATCH = "chunk_dispatch"
LEAF_SPANS = ENTRY_SPANS + DRIVER_SPANS + (DISPATCH, "checkpoint_save")


# -- a minimal reader of the protobuf wire format -----------------------------

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    varint and fixed fields, a ``memoryview`` for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            value = int.from_bytes(buf[i:i + width], "little")
            i += width
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_values(buf):
    """The value of one map entry (key = field 1, value = field 2)."""
    return next((v for f, v in _fields(buf) if f == 2), b"")


def _plane_scopes(plane) -> dict:
    """``{op name: tf_op}`` of one XPlane message; empty unless it is a
    device plane."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:                                   # XPlane.name
            name = bytes(v).decode()
        elif f == 4:                                 # event_metadata
            events.append(_map_values(v))
        elif f == 5:                                 # stat_metadata
            sid, sname = 0, ""
            for g, w in _fields(_map_values(v)):
                if g == 1:
                    sid = w
                elif g == 2:
                    sname = bytes(w).decode()
            stat_names[sid] = sname
    if not name.startswith("/device:"):
        return {}
    tf_op_ids = {i for i, s in stat_names.items() if s == TF_OP}
    out = {}
    for meta in events:
        text, tf_op = "", None
        for f, v in _fields(meta):
            if f == 2:                               # XEventMetadata.name
                text = bytes(v).decode()
            elif f == 5:                             # XEventMetadata.stats
                stat = dict(_fields(v))
                if stat.get(1) in tf_op_ids:
                    if 5 in stat:                    # str_value
                        tf_op = bytes(stat[5]).decode()
                    elif 7 in stat:                  # ref_value
                        tf_op = stat_names.get(stat[7])
        if text and tf_op is not None:
            out[xplane.op_name(text)] = tf_op
    return out


def op_scopes(path: str) -> dict:
    """``{op name: tf_op}`` for the device ops of a ``.xplane.pb``, op
    names as ``bench.xplane.op_name`` gives them."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = {}
    for f, plane in _fields(data):
        if f == 1:                                   # XSpace.planes
            out.update(_plane_scopes(plane))
    return out


def in_scope(tf_op: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the name stack ``tf_op``."""
    return scope in tf_op.split("/")


def scope_s(device, scopes: dict, names) -> float:
    """Device seconds (averaged over planes) of the ops whose ``tf_op``
    holds any of the scopes ``names``. ``device`` is a
    ``bench.xplane.DeviceTime``."""
    s = sum(t for op, t in device.op_s.items()
            if any(in_scope(scopes.get(op, ""), n) for n in names))
    return s / max(device.planes, 1)


# -- host spans ---------------------------------------------------------------

def spans(profile, lo: float, hi: float, names=LEAF_SPANS) -> list:
    """The program's host spans named in ``names`` that overlap
    ``[lo, hi]``, as ``bench.xplane.Event``s in start order."""
    return [e for e in xplane.host_events(profile, "")
            if e.name in names and e.end_ns > lo and e.start_ns < hi]


def split(host_spans) -> tuple:
    """``(entry, driver)`` spans of one call: validation, build and the
    first dispatch (which traces, lowers and loads the program), against
    the later dispatches, every offload and the collection."""
    dispatch = [e for e in host_spans if e.name == DISPATCH]
    entry = [e for e in host_spans if e.name in ENTRY_SPANS] + dispatch[:1]
    driver = [e for e in host_spans if e.name in DRIVER_SPANS] + dispatch[1:]
    return entry, driver


def idle_inside(idle, host_spans) -> float:
    """Seconds of the idle ``(start, end)`` gaps that fall inside the
    union of ``host_spans``."""
    covered = xplane.union(host_spans)
    return sum(max(0.0, min(e, b) - max(s, a))
               for s, e in idle for a, b in covered) / 1e9


def label_gaps(idle, host_spans, lo: float, top: int = 10) -> list:
    """The ``top`` longest idle gaps, each named by the program span that
    overlaps it most: ``[[label, seconds], ...]``."""
    named = [(e.name, e.start_ns, e.end_ns) for e in host_spans]
    out = []
    for g in sorted(idle, key=lambda g: g[0] - g[1])[:top]:
        label = xplane.attribute(g, named)
        out.append([f"{label} at +{(g[0] - lo) / 1e9:.3f} s",
                    (g[1] - g[0]) / 1e9])
    return out


def readings(path: str, rounds: int, annotation: str = "bench.call") -> dict:
    """The per-layer numbers of the one call that ``annotation`` spans in
    the trace at ``path``: device milliseconds per round under the
    ``op_stream`` scope and under ``round_metrics`` or ``convergence``,
    device-idle seconds inside the entry's and the scan driver's spans
    (``split``), and the longest idle gaps named by span."""
    profile = xplane.load(path)
    (call,) = xplane.host_events(profile, annotation)
    lo, hi = call.start_ns, call.end_ns
    device = xplane.device_time(profile, lo, hi)
    scopes = op_scopes(path)
    host = spans(profile, lo, hi)
    entry, driver = split(host)
    return {
        "op_stream.ms_per_round":
            scope_s(device, scopes, ["op_stream"]) / rounds * 1e3,
        "round.metrics_ms_per_round":
            scope_s(device, scopes, ["round_metrics", "convergence"])
            / rounds * 1e3,
        "entry.idle_s_per_call": idle_inside(device.idle, entry),
        "driver.idle_s_per_call": idle_inside(device.idle, driver),
        "idle_gaps": label_gaps(device.idle, host, lo),
    }


class CacheRead:
    """Seconds JAX spent reading executables from its persistent compile
    cache while entered (its ``cache_retrieval_time_sec`` events)."""

    def __init__(self):
        self.seconds = 0.0

    def _on_duration(self, event, duration, **_):
        if event == CACHE_READ_EVENT:
            self.seconds += duration

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
