"""What the per-layer metric readers share: sums of the engine's spans and
counters over the measured window, per query."""
from __future__ import annotations

from typing import Any, Iterable, Optional


def span_ms_per_query(ctx: Any, names: Iterable[str]) -> Optional[float]:
    """Milliseconds inside the named ``repro.obs`` spans per query, a span
    nested in another of the names counted once (through its outermost)."""
    names = set(names)
    if not ctx.n_queries or not ctx.spans:
        return None
    by_id = {s.id: s for s in ctx.spans}

    def nested(s) -> bool:
        p = s.parent
        while p is not None and p in by_id:
            if by_id[p].name in names:
                return True
            p = by_id[p].parent
        return False

    picked = [s for s in ctx.spans if s.name in names and not nested(s)]
    if not picked:
        return None
    return sum(s.dur_ms for s in picked) / ctx.n_queries


def counter_per_query(ctx: Any, name: str) -> Optional[float]:
    if not ctx.n_queries or name not in ctx.counters:
        return None
    return ctx.counters[name] / ctx.n_queries


def idle_percent(ctx: Any) -> Optional[float]:
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    return 100.0 * ctx.device.idle_share
