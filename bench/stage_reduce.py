"""The trace reduction by engine stage and device program.

``bench/trace_reduce.py`` charges an idle gap to a whole query and device
time to unnamed ops.  This reduction reads two more things from the same
``.xplane.pb``:

- the engine's own spans: an enabled ``repro.obs.Tracer`` marks each span
  on the profiler's clock as a host event named ``repro.<span name>``;
- the ``XLA Modules`` line of each device plane: which program ran when.
  A module's name is the jitted function's (``jit_`` prefix and ``(hash)``
  suffix stripped): ``q_groupby``, ``chunk_agg``, ...

From them: device seconds by module, op self time by ``<module>/<op>``, and
each idle gap named ``bench.q.<template>/<stage>``, where the stage is the
innermost ``repro.*`` span open at the gap's midpoint, taken as the
latest-starting open span across threads.  A gap with no stage open keeps
the query's name, and one outside every query stays ``no query in
flight``.  Busy, compute and window times are the accepted reduction's.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench.trace_reduce import (
    IDLE, OP_LINES, QUERY_PREFIX, WINDOW, Event, TraceSummary, _DEVICE_PLANE, clip, gaps,
    op_name, self_times, summarize as base_summarize, union,
)

STAGE_PREFIX = "repro."
MODULE_LINE = "XLA Modules"
# stages no finer than the query: a gap charged to them is not explained
COARSE_STAGES = ("query", "execute")
_HASH = re.compile(r"\(\d+\)$")


def module_name(full: str) -> str:
    """``jit_chunk_agg(1234)`` -> ``chunk_agg``."""
    name = _HASH.sub("", full)
    return name[4:] if name.startswith("jit_") else name


@dataclass
class StageSummary:
    base: TraceSummary                                           # the accepted reduction
    module_s: Dict[str, float] = field(default_factory=dict)     # device seconds by module
    op_s: Dict[str, float] = field(default_factory=dict)         # self time by <module>/<op>
    idle_gaps: Dict[str, float] = field(default_factory=dict)    # by query and stage

    def in_query_idle_s(self) -> float:
        return sum(v for k, v in self.idle_gaps.items() if k != IDLE)

    def named_idle_s(self) -> float:
        """In-query idle seconds charged to a stage finer than the query."""
        return sum(v for k, v in self.idle_gaps.items()
                   if "/" in k and k.rsplit("/", 1)[1] not in COARSE_STAGES)

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.idle_gaps.items(), key=lambda kv: -kv[1])[:n]]


class _Open:
    """The latest-starting span open at a time, over spans of any thread."""

    def __init__(self, spans: List[Event]):
        self.spans = sorted(spans, key=lambda e: e.start)
        self.starts = [e.start for e in self.spans]

    def at(self, t: float) -> Optional[Event]:
        i = bisect.bisect_right(self.starts, t)
        while i > 0:
            i -= 1
            if self.spans[i].end >= t:
                return self.spans[i]
        return None


def attribute_stages(gap_list: List[Tuple[float, float]], host: List[Event]) -> Dict[str, float]:
    queries = _Open([e for e in host if e.name.startswith(QUERY_PREFIX)])
    stages = _Open([e for e in host if e.name.startswith(STAGE_PREFIX)])
    out: Dict[str, float] = {}
    for s, e in gap_list:
        mid = 0.5 * (s + e)
        q = queries.at(mid)
        name = IDLE
        if q is not None:
            st = stages.at(mid)
            name = q.name if st is None else f"{q.name}/{st.name[len(STAGE_PREFIX):]}"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def _with_modules(ops: List[Event], modules: List[Event]) -> List[Event]:
    """Each op renamed ``<module>/<op>`` by the module run it starts in."""
    mods = sorted(modules, key=lambda e: e.start)
    starts = [m.start for m in mods]
    out = []
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        mod = mods[i].name if i >= 0 and mods[i].end >= e.start else "?"
        out.append(Event(f"{mod}/{e.name}", e.start, e.end))
    return out


def summarize(device_ops: Dict[str, List[Event]], modules: Dict[str, List[Event]],
              host: List[Event]) -> StageSummary:
    """``device_ops`` and ``modules``: per device, its op and module events;
    ``host``: the harness's annotations and the engine's spans."""
    base = base_summarize(device_ops, [e for e in host if e.name.startswith("bench.")])
    windows = [e for e in host if e.name == WINDOW]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        evs = [e for ops in device_ops.values() for e in ops]
        lo, hi = min(e.start for e in evs), max(e.end for e in evs)
    n = max(1, len(device_ops))
    out = StageSummary(base)
    for plane, ops in device_ops.items():
        mods = modules.get(plane, [])
        for e in mods:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                out.module_s[e.name] = out.module_s.get(e.name, 0.0) + d / n
        for k, v in self_times(_with_modules(ops, mods), lo, hi).items():
            out.op_s[k] = out.op_s.get(k, 0.0) + v / n
        merged = union(clip(((e.start, e.end) for e in ops), lo, hi))
        for k, v in attribute_stages(gaps(merged, lo, hi), host).items():
            out.idle_gaps[k] = out.idle_gaps.get(k, 0.0) + v / n
    return out


def load(path: str) -> Tuple[Dict[str, List[Event]], Dict[str, List[Event]], List[Event]]:
    """Device op and module events, and the host's ``bench.*`` and
    ``repro.*`` events, of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []

    def events(line, name=lambda s: s, keep=lambda s: True):
        return [Event(name(e.name), e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                for e in line.events if keep(e.name)]

    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in OP_LINES:
                    device_ops.setdefault(plane.name, []).extend(events(line, op_name))
                elif line.name == MODULE_LINE:
                    modules.setdefault(plane.name, []).extend(events(line, module_name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(events(line, keep=lambda s: s.startswith(("bench.", STAGE_PREFIX))))
    device_ops = {k: v for k, v in device_ops.items() if v}
    return device_ops, {k: v for k, v in modules.items() if k in device_ops}, host


def reduce_file(path: str) -> Optional[StageSummary]:
    device_ops, modules, host = load(path)
    if not device_ops:
        return None
    return summarize(device_ops, modules, host)
