"""From the profiler's trace to per-layer numbers.

A traced run wraps its window in the host span ``window`` and each step's
parts in the spans ``schedule_build``, ``skipper_match`` and ``fetch_mask``
(``jax.profiler.TraceAnnotation``). The trace puts those spans and the
device's operations on one clock:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane; an event's name is the operation's HLO text, whose instruction
  name (``%skipper_boundary_kernel.3 = ...``) gives the op, and a Pallas
  kernel's op is the ``name`` of its ``pallas_call``;
* host spans: events of the ``/host:CPU`` plane with one of the span names.

``summarize`` reduces those events to the window's length, the time in
which an operation ran on each device (the union of op intervals), each
op's summed device time and count, the spans' durations, and the idle gaps
of the first device, each named by the span the host was in.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
TOP = 10  # entries of each breakdown list

_OP = re.compile(r"%?([A-Za-z_][\w\-.]*?)(?:\s*=|$)")
_SUFFIX = re.compile(r"\.\d+$")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def op_name(text: str) -> str:
    """``%skipper_boundary_kernel.3 = (u8[...]) custom-call(...)`` ->
    ``skipper_boundary_kernel.3``; text that is no HLO instruction is
    kept."""
    m = _OP.match(text)
    return m.group(1) if m else text


def op_base(name: str) -> str:
    """``skipper_boundary_kernel.3`` -> ``skipper_boundary_kernel``: every
    instruction that one ``pallas_call`` name became."""
    return _SUFFIX.sub("", name)


def read_xplane(path: str, spans: Iterable[str]) -> list:
    """The device ops and the named host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    spans = set(spans) | {WINDOW}
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for e in line.events:
                name = op_name(e.name) if device else e.name
                if device or name in spans:
                    out.append(Event(plane.name, line.name, name,
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def union(intervals: Iterable[tuple]) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # length of the traced window
    busy_s: float                   # device busy time, mean over devices
    op_s: dict                      # op name -> summed device seconds
    op_calls: dict                  # op name -> events in the window
    span_s: dict                    # span name -> [seconds of each]
    idle_gaps: list                 # [[span name, seconds]], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel(self, name: str) -> tuple:
        """(summed device seconds, events) of every op named ``name``."""
        keys = [k for k in self.op_s if op_base(k) == name]
        return (sum(self.op_s[k] for k in keys),
                sum(self.op_calls[k] for k in keys))

    def top_ops(self) -> list:
        ranked = sorted(self.op_s.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ranked[:TOP]]


def summarize(events: list) -> TraceSummary:
    """Reduce one traced window (see the module doc)."""
    windows = [e for e in events if e.plane == HOST_PLANE and e.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    ops = [e for e in events if e.plane.startswith(DEVICE_PLANE)
           and e.end_ns > lo and e.start_ns < hi]
    planes = sorted({e.plane for e in events
                     if e.plane.startswith(DEVICE_PLANE)})
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy = {}
    for p in planes:
        busy[p] = union(_clip([(e.start_ns, e.end_ns) for e in ops
                               if e.plane == p], lo, hi))
    op_s, op_calls = {}, {}
    for e in ops:
        op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns * 1e-9
        op_calls[e.name] = op_calls.get(e.name, 0) + 1
    span_s = {}
    host = [e for e in events if e.plane == HOST_PLANE and e.name != WINDOW
            and e.end_ns > lo and e.start_ns < hi]
    for e in host:
        span_s.setdefault(e.name, []).append(e.dur_ns * 1e-9)
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(sum(e - s for s, e in b) for b in busy.values())
        * 1e-9 / len(planes),
        op_s=op_s,
        op_calls=op_calls,
        span_s=span_s,
        idle_gaps=_named_gaps(busy[planes[0]], lo, hi, host),
    )


def _named_gaps(busy, lo, hi, spans) -> list:
    """The longest idle gaps of one device in [lo, hi], each named by the
    host span that overlaps it most (``other`` where none does)."""
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    named = []
    for s, e in gaps:
        best, name = 0.0, "other"
        for sp in spans:
            overlap = min(e, sp.end_ns) - max(s, sp.start_ns)
            if overlap > best:
                best, name = overlap, sp.name
        named.append([name, (e - s) * 1e-9])
    named.sort(key=lambda g: -g[1])
    return named[:TOP]
