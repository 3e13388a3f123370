"""Reduction of a JAX profiler trace (``.xplane.pb``) to device time.

Device operations are the events of the ``XLA Ops`` line of each
``/device:...`` plane; an event's name is the HLO instruction's text, and
the reduction names it by the instruction (``round_step_2d.12`` for
``%round_step_2d.12 = (...) custom-call(...)``). Busy time is the union of
the op intervals inside a window, the idle share is 1 minus busy over the
window, and a kernel's time is the sum of the durations of its events. An
op that holds others (a ``while`` loop around its body) counts towards
busy time but not towards per-op time, so no time is counted twice there.
The window is the span of a host annotation the benchmark wrote around the
traced call.
"""

from __future__ import annotations

import collections
import dataclasses
import re

OPS_LINE = "XLA Ops"
_INSTRUCTION = re.compile(r"%?([\w.\-]+)\s*=")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float
    container: bool = False     # holds other ops of its line


def op_name(text: str) -> str:
    """The HLO instruction name of a device op event's text."""
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def device_events(profile) -> dict:
    """``{plane name: [Event]}`` of every device plane's op line."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            ev = sorted(((e.start_ns, e.start_ns + e.duration_ns,
                          op_name(e.name)) for e in line.events))
            # ops of one line run one after another, so an op that the
            # next one starts inside holds it
            out[plane.name] = [
                Event(n, s, e, i + 1 < len(ev) and ev[i + 1][0] < e)
                for i, (s, e, n) in enumerate(ev)]
    return out


def host_events(profile, prefix: str) -> list:
    """Host events whose name starts with ``prefix``."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name.startswith(prefix))
    return sorted(out, key=lambda e: e.start_ns)


def clip(events, lo: float, hi: float) -> list:
    return [Event(e.name, max(e.start_ns, lo), min(e.end_ns, hi),
                  e.container)
            for e in events if e.end_ns > lo and e.start_ns < hi]


def union(events) -> list:
    """Merged ``(start, end)`` intervals covered by ``events``."""
    merged = []
    for e in sorted(events, key=lambda e: e.start_ns):
        if merged and e.start_ns <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e.end_ns)
        else:
            merged.append([e.start_ns, e.end_ns])
    return [tuple(m) for m in merged]


def gaps(busy, lo: float, hi: float) -> list:
    """Idle ``(start, end)`` intervals of ``[lo, hi]`` outside ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class DeviceTime:
    window_s: float
    busy_s: float                 # averaged over device planes
    op_s: dict                    # op name -> seconds, summed over planes
                                  # (ops that hold others left out)
    idle: list                    # (start_ns, end_ns) gaps of one plane
    planes: int

    def kernel_s(self, prefix: str) -> float:
        """Seconds of the ops whose name starts with ``prefix``, summed
        over the device planes and averaged over them."""
        return sum(s for n, s in self.op_s.items()
                   if n.startswith(prefix)) / max(self.planes, 1)


def device_time(profile, lo: float, hi: float) -> DeviceTime:
    """Device busy time, per-op time and idle gaps inside ``[lo, hi]``."""
    planes = device_events(profile)
    op_s = collections.Counter()
    busy = 0.0
    idle = []
    for i, (_, events) in enumerate(sorted(planes.items())):
        events = clip(events, lo, hi)
        for e in events:
            if not e.container:
                op_s[e.name] += (e.end_ns - e.start_ns) / 1e9
        covered = union(events)
        busy += sum(e - s for s, e in covered) / 1e9
        if i == 0:
            idle = gaps(covered, lo, hi)
    n = len(planes)
    return DeviceTime(window_s=(hi - lo) / 1e9,
                      busy_s=busy / n if n else 0.0,
                      op_s=dict(op_s), idle=idle, planes=n)


def attribute(gap, spans, instants=(), phases=()) -> str:
    """What the host was doing in an idle ``gap``: the label of the host
    span in ``spans`` that overlaps it most; else of an instant in
    ``instants`` that falls inside it; else of the phase in ``phases``
    that holds its midpoint; else ``"host: other"``. Spans and phases are
    ``(label, start_ns, end_ns)``, instants ``(label, at_ns)``."""
    s, e = gap
    best, best_ov = None, 0.0
    for label, a, b in spans:
        ov = min(b, e) - max(a, s)
        if ov > best_ov:
            best, best_ov = label, ov
    if best is not None:
        return best
    for label, at in instants:
        if s <= at <= e:
            return label
    mid = (s + e) / 2
    for label, a, b in phases:
        if a <= mid <= b:
            return label
    return "host: other"
