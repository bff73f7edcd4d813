"""From the profiler's trace to numbers: the one reduction every PR shares.

``read_xplane`` turns the ``.xplane.pb`` jax's profiler writes into plain
lists; everything below it is arithmetic on ``(name, start_ns, dur_ns)``
tuples and is tested against a small recorded trace
(``tests/benchmark/data/small_trace.json``).

Device operations are the events of the lines named ``XLA Ops`` on planes
named ``/device:...``; host spans are the events on ``/host:CPU`` whose names
carry the prefixes the program (``lz.``) and the benchmark (``bench.``) use.
Both sit on the profiler's one clock.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, dur_ns
Interval = Tuple[float, float]            # start_ns, end_ns

SPAN_PREFIXES = ("lz.", "bench.")
WINDOW_SPAN = "bench.window"
OP_LINES = ("XLA Ops",)
NO_SPAN = "_no_span_"


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no trace under {trace_dir}")
    return paths[-1]


_HLO_RE = re.compile(r"^%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+\[[0-9,]*\])?")


def op_label(name: str) -> str:
    """A device event is named by its whole HLO instruction
    (``%fusion.7 = f32[64,128]{1,0:T(8,128)} fusion(...)``); the label keeps
    the instruction's name and result shape — ``fusion.7_f32_64_128_`` — so
    that two programs' ``fusion.7`` stay apart in a breakdown."""
    m = _HLO_RE.match(name)
    if not m:
        return name[:64]
    shape = re.sub(r"[^A-Za-z0-9]+", "_", m.group(2)) if m.group(2) else ""
    return (m.group(1) + ("_" + shape if shape else ""))[:64]


def read_xplane(path: str) -> dict:
    """{"devices": {plane: [Event]}, "spans": [Event]} of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops: List[Event] = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    ops.append((op_label(e.name), float(e.start_ns),
                                float(e.duration_ns)))
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.duration_ns)))
    return {"devices": devices, "spans": spans}


# --------------------------------------------------------------------------
# Arithmetic on intervals.
# --------------------------------------------------------------------------

def window_of(trace: dict) -> Interval:
    """The measured window on the trace's clock: the benchmark's own span."""
    w = [(s, s + d) for n, s, d in trace["spans"] if n == WINDOW_SPAN]
    if len(w) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
    return w[0]


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those wholly outside are dropped."""
    lo, hi = window
    out = []
    for n, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((n, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Interval]:
    """Merged, sorted intervals in which at least one event runs."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[Interval] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


class Busy:
    """Merged busy intervals with the nanoseconds busy inside any range."""

    def __init__(self, intervals: Sequence[Interval]):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.cum = [0.0]
        for a, b in intervals:
            self.cum.append(self.cum[-1] + (b - a))

    def inside(self, lo: float, hi: float) -> float:
        i = bisect.bisect_right(self.ends, lo)
        j = bisect.bisect_left(self.starts, hi)
        if i >= j:
            return 0.0
        return (self.cum[j] - self.cum[i] - max(0.0, lo - self.starts[i])
                - max(0.0, self.ends[j - 1] - hi))


def device_busy(trace: dict) -> Dict[str, float]:
    """busy_s averaged over the device planes, window_s, idle share."""
    lo, hi = window = window_of(trace)
    if not trace["devices"]:
        raise ValueError("the trace holds no device plane with XLA ops")
    busy = [total(union(clip(ops, window))) for ops in trace["devices"].values()]
    busy_s = sum(busy) / len(busy) / 1e9
    window_s = (hi - lo) / 1e9
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s}


def spans_named(trace: dict, name: str) -> List[Event]:
    return clip([e for e in trace["spans"] if e[0] == name], window_of(trace))


def _first_device_busy(trace: dict, window: Interval) -> Busy:
    ops = next(iter(trace["devices"].values()))
    return Busy(union(clip(ops, window)))


def device_ns_per_span(trace: dict, prefix: str) -> List[float]:
    """Device-busy nanoseconds inside each host span whose name starts with
    ``prefix`` (first device plane; a dispatch ends in a readback, so its
    operations run inside its span — and, once two dispatches are open at
    once, inside the other's too: the metrics read
    ``device_ns_per_dispatch``, ``span_study`` this)."""
    window = window_of(trace)
    if not trace["devices"]:
        return []
    busy = _first_device_busy(trace, window)
    return [busy.inside(s, s + d) for n, s, d in clip(trace["spans"], window)
            if n.startswith(prefix)]


def device_ns_per_dispatch(trace: dict, prefix: str) -> Optional[float]:
    """Device-busy nanoseconds inside the UNION of the host spans whose name
    starts with ``prefix``, over the number of those spans (first device
    plane): a dispatch's device time, read once whether dispatches overlap
    or not. Where the spans are disjoint the union is their sum, and this is
    the mean of ``device_ns_per_span``; where two are open at once (PR 30)
    each of those also counts part of the other's pass. None without a
    device plane or such a span."""
    window = window_of(trace)
    spans = [e for e in clip(trace["spans"], window) if e[0].startswith(prefix)]
    if not trace["devices"] or not spans:
        return None
    busy = _first_device_busy(trace, window)
    return sum(busy.inside(a, b) for a, b in union(spans)) / len(spans)


def dispatch_study(trace: dict, prefix: str, kernel: str) -> dict:
    """Study aid: one trace's device milliseconds a ``prefix*`` span read
    three ways — the mean over the spans of the busy time inside each
    (``device_ns_per_span``), the busy time inside their union over their
    number (``device_ns_per_dispatch``), and the first plane's operations
    summed by name over that number, the ``kernel*`` ones apart from the
    union of the rest."""
    window = window_of(trace)
    per = device_ns_per_span(trace, prefix)
    if not per:
        return {}
    ops = clip(next(iter(trace["devices"].values())), window)
    named = sum(d for name, _, d in ops if name.startswith(kernel))
    rest = total(union(e for e in ops if not e[0].startswith(kernel)))
    n = len(per)
    return {"spans": n, "each_span_ms": sum(per) / n / 1e6,
            "union_ms": device_ns_per_dispatch(trace, prefix) / 1e6,
            "by_name_ms": {kernel: named / n / 1e6, "rest": rest / n / 1e6,
                           "sum": (named + rest) / n / 1e6}}


def top_ops(trace: dict, n: int = 10) -> List[List]:
    """[[name, seconds]] of the device operations that took most time."""
    window = window_of(trace)
    acc: Dict[str, float] = {}
    for ops in trace["devices"].values():
        for name, _, d in clip(ops, window):
            acc[name] = acc.get(name, 0.0) + d
    k = max(1, len(trace["devices"]))
    return [[name, ns / k / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps_by_span(trace: dict, n: int = 10) -> List[List]:
    """[[span, seconds]]: the device's idle time inside the window, each
    nanosecond credited to the innermost (latest-started) host span that
    covers it, or to ``_no_span_``."""
    lo, hi = window = window_of(trace)
    if not trace["devices"]:
        return []
    busy = _first_device_busy(trace, window)
    spans = sorted((s, s + d, name) for name, s, d
                   in clip(trace["spans"], window) if name != WINDOW_SPAN)
    points = sorted({lo, hi} | {p for s, e, _ in spans for p in (s, e)})
    acc: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []      # heap on -start
    nxt = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(spans) and spans[nxt][0] <= a:
            s, e, name = spans[nxt]
            heapq.heappush(active, (-s, e, name))
            nxt += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        name = active[0][2] if active else NO_SPAN
        idle = (b - a) - busy.inside(a, b)
        if idle > 0:
            acc[name] = acc.get(name, 0.0) + idle
    return [[name, ns / 1e9] for name, ns in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def span_study(trace: dict, outer: str, n: int = 6) -> dict:
    """Study aid: how many spans of each name the window holds, and the
    longest ``outer`` spans and the longest gaps between them, each with the
    device-busy time inside — to tell a stall of the host from one of the
    device."""
    window = window_of(trace)
    spans = clip(trace["spans"], window)
    names: Dict[str, List[float]] = {}
    for name, _, d in spans:
        names.setdefault(name, []).append(d)
    busy = _first_device_busy(trace, window) if trace["devices"] else None
    outs = sorted((s, s + d) for name, s, d in spans if name == outer)
    inner = sorted((s, s + d, name) for name, s, d in spans
                   if name.startswith(outer.rsplit(".", 1)[0]) and name != outer)

    def describe(a, b):
        kids = [(e - s_) / 1e6 for s_, e, _ in inner if a <= s_ < b]
        return {"at_s": (a - window[0]) / 1e9, "ms": (b - a) / 1e6,
                "children_ms": kids[:4],
                "device_ms": busy.inside(a, b) / 1e6 if busy else None}

    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(outs, outs[1:])]
    return {
        "counts": {k: {"n": len(v), "mean_ms": sum(v) / len(v) / 1e6}
                   for k, v in names.items()},
        "longest": [describe(a, b) for a, b in
                    sorted(outs, key=lambda x: x[0] - x[1])[:n]],
        "longest_gaps": [describe(a, b) for a, b in
                         sorted(gaps, key=lambda x: x[0] - x[1])[:n]],
    }
