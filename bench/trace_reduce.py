"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time (the union of the intervals in which an
operation ran), the traced window, device time by operation name, and the
device's idle gaps attributed to what the harness was doing on the host.

The harness marks the window with a ``jax.profiler.TraceAnnotation`` named
``bench.window`` and each query with one named ``bench.q.<template>``; a
gap is charged to the innermost such span open at its midpoint, or to
``no query in flight``.  Only the process that held the chip can trace it,
so the reduction runs in the benchmark's own process once the window has
closed.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
QUERY_PREFIX = "bench.q."
IDLE = "no query in flight"
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# lines of a device plane that hold one event per operation
OP_LINES = ("XLA Ops",)
# transfers between host and device: not part of a program's compute time
_TRANSFER = re.compile(r"(?i)(host.?to.?device|device.?to.?host|transfer|infeed|outfeed|h2d|d2h)")

Interval = Tuple[float, float]


def op_name(full: str) -> str:
    """An HLO op's name without its operands and attributes, with the
    custom-call target where there is one (the Pallas kernels')."""
    head = full.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', full)
    return f"{head} {m.group(1)}" if m else head


@dataclass
class Event:
    name: str
    start: float   # seconds, on the trace's common clock
    end: float


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                  # mean over the devices traced
    compute_s: float               # op time less transfers, mean over devices
    n_devices: int
    op_s: Dict[str, float] = field(default_factory=dict)       # self time, mean over devices
    idle_gaps: Dict[str, float] = field(default_factory=dict)  # by host span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(gap_list: List[Interval], spans: List[Event]) -> Dict[str, float]:
    """Seconds of idle device time by the innermost harness span open at
    each gap's midpoint."""
    out: Dict[str, float] = {}
    spans = sorted(spans, key=lambda e: e.start)
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        name = IDLE
        for sp in spans:
            if sp.start > mid:
                break
            if sp.end >= mid:
                name = sp.name  # a later start is the inner one
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def self_times(ops: List[Event], lo: float, hi: float) -> Dict[str, float]:
    """Seconds by op name inside ``[lo, hi]``, each op less the ops nested in
    it on the same device (a while loop less its body's fusions), so the
    names add up to the busy time."""
    out: Dict[str, float] = {}
    stack: List[List] = []   # [event, seconds of nested children]

    def close(entry: List) -> None:
        e, inner = entry
        d = min(e.end, hi) - max(e.start, lo)
        if d > 0:
            out[e.name] = out.get(e.name, 0.0) + max(0.0, d - inner)

    for e in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            close(stack.pop())
        if stack:
            stack[-1][1] += max(0.0, min(e.end, hi) - max(e.start, lo))
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return out


def summarize(device_ops: Dict[str, List[Event]], host: List[Event]) -> TraceSummary:
    """``device_ops``: per device, its operation events; ``host``: the
    harness's annotation events."""
    windows = [e for e in host if e.name == WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        evs = [e for ops in device_ops.values() for e in ops]
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    n = max(1, len(device_ops))
    busy = compute = 0.0
    op_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    queries = [e for e in host if e.name.startswith(QUERY_PREFIX)]
    for ops in device_ops.values():
        merged = union(clip(((e.start, e.end) for e in ops), lo, hi))
        busy += sum(e - s for s, e in merged)
        compute += sum(e - s for s, e in union(clip(
            ((e.start, e.end) for e in ops if not _TRANSFER.search(e.name)), lo, hi)))
        for k, v in self_times(ops, lo, hi).items():
            op_s[k] = op_s.get(k, 0.0) + v / n
        for k, v in attribute(gaps(merged, lo, hi), queries).items():
            idle[k] = idle.get(k, 0.0) + v / n
    return TraceSummary(hi - lo, busy / n, compute / n, len(device_ops), op_s, idle)


def load(path: str) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """Device operation events and harness annotations of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            evs = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs.extend(Event(op_name(e.name), e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name.startswith("bench."))
    return {k: v for k, v in device_ops.items() if v}, host


def reduce_file(path: str) -> Optional[TraceSummary]:
    device_ops, host = load(path)
    if not device_ops:
        return None
    return summarize(device_ops, host)
