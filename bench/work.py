"""Operations and bytes a query's aggregation needs, from its shapes: the
yardstick for roofline shares."""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

from bench.query import is_agg

_NAME = re.compile(r"[A-Za-z_]\w*")


def groupby_work(q: Dict[str, Any], tables) -> Optional[Tuple[float, float]]:
    """``(adds, bytes)`` of a one-table GROUP BY with no filter: the key and
    every aggregated column read once, and one 4-byte accumulator per key of
    the key space (max key + 1) and aggregate written once.  None for any
    other query."""
    if len(q["from"]) != 1 or not q.get("group_by") or q.get("where"):
        return None
    (table, _), = q["from"]
    cols = tables[table]
    key = cols[q["group_by"]]
    n = len(key)
    aggs = [it for it in q["select"] if is_agg(it)]
    read = {q["group_by"]} | {c for _, e in aggs for c in _NAME.findall(e) if c in cols}
    key_space = int(key.max()) + 1 if n else 0
    nbytes = sum(n * cols[c].dtype.itemsize for c in read) + 4 * key_space * len(aggs)
    return float(n * len(aggs)), float(nbytes)


def groupby_seconds(q: Dict[str, Any], tables, peaks: Dict[str, float]) -> Optional[float]:
    """The least time the work takes: bytes at HBM peak, or adds at the
    FLOP/s peak where that is larger."""
    w = groupby_work(q, tables)
    if w is None:
        return None
    adds, nbytes = w
    return max(nbytes / peaks["hbm_bytes_per_s"], adds / peaks["flops_per_s"])
