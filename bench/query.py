"""A traffic mix's queries, written as data and rendered for the engine.

A query is a JSON object:

    {"from": [["rankings", "r"], ["uservisits", "uv"]],    # 1 or 2 tables
     "join": ["r.pageURL", "uv.destURL"],                  # equi-join, 2 tables
     "where": [["uv.visitDate", ">=", 3652], ["x", "<", ":p"]],
     "select": ["uv.sourceIP", ["sum", "uv.adRevenue"], ["avg", "r.pageRank"]],
     "group_by": "uv.sourceIP",
     "order_by": [1, "desc"],                              # select position
     "limit": 1}

``where`` is a conjunction; its right-hand sides are numbers or ``:name``
parameters bound per request.  Expressions are arithmetic over columns
(``a.col`` or a bare ``col``), numbers and ``+ - * /`` with parentheses,
which read the same in SQL and in Python.  The engine gets the SQL text
(or a MapReduce job); ``bench/reference.py`` evaluates the same object.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

AGGS = ("sum", "count", "avg", "min", "max")
OPS = ("=", "<", "<=", ">", ">=", "!=")


def item_text(item: Any) -> str:
    if isinstance(item, str):
        return item
    agg, expr = item
    if agg not in AGGS:
        raise ValueError(f"unknown aggregate {agg!r}")
    return f"{agg.upper()}({expr})"


def is_agg(item: Any) -> bool:
    return not isinstance(item, str)


def key_position(q: Dict[str, Any]) -> Optional[int]:
    """Select position of the GROUP BY column, or None for a scalar query."""
    g = q.get("group_by")
    if g is None:
        return None
    for i, it in enumerate(q["select"]):
        if it == g:
            return i
    raise ValueError(f"GROUP BY {g!r} is not selected")


def to_sql(q: Dict[str, Any]) -> str:
    tables = ", ".join(t if a is None else f"{t} {a}" for t, a in q["from"])
    preds: List[str] = []
    if q.get("join"):
        left, right = q["join"]
        preds.append(f"{left} = {right}")
    for col, op, rhs in q.get("where", []):
        if op not in OPS:
            raise ValueError(f"unknown comparison {op!r}")
        preds.append(f"{col} {op} {rhs}")
    sql = f"SELECT {', '.join(item_text(it) for it in q['select'])} FROM {tables}"
    if preds:
        sql += " WHERE " + " AND ".join(preds)
    if q.get("group_by"):
        sql += f" GROUP BY {q['group_by']}"
    if q.get("order_by"):
        pos, direction = q["order_by"]
        sql += f" ORDER BY {item_text(q['select'][pos])} {direction.upper()}"
    if q.get("limit") is not None:
        sql += f" LIMIT {int(q['limit'])}"
    return sql


def mapreduce_args(q: Dict[str, Any]) -> Tuple[str, str, Optional[str], str]:
    """``(table, key, value column or None for a count, reduce op)`` of a
    one-table, one-aggregate GROUP BY: the shape a MapReduce job has."""
    (table, _), = q["from"]
    key = q["group_by"]
    if key is None or q.get("where") or q.get("order_by") or len(q["select"]) != 2:
        raise ValueError("a MapReduce job is a one-table GROUP BY with one aggregate")
    agg, expr = next(it for it in q["select"] if is_agg(it))
    if agg == "count":
        return table, key, None, "+"
    op = {"sum": "+", "min": "min", "max": "max"}.get(agg)
    if op is None:
        raise ValueError(f"no MapReduce reduce for {agg!r}")
    return table, key, expr, op
