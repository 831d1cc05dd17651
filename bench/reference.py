"""Plain numpy evaluation of a query object (``bench/query.py``) over the
generated tables: the reference that decides ``correct``.  It imports
nothing of the engine.

Filters compare each column in its stored dtype with the parameter cast to
that dtype, as the engine does.  Aggregated values are computed in
``precision``: ``float64`` for the reference; ``bfloat16`` for the control,
which rounds every float column and every arithmetic result to bfloat16 (a
column stored in bfloat16) and sums in float64.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from bench.query import is_agg, key_position

Tables = Dict[str, Dict[str, np.ndarray]]
_COL = re.compile(r"\b([A-Za-z_]\w*)\.([A-Za-z_]\w*)\b")
_CMP = {
    "=": np.equal, "!=": np.not_equal, "<": np.less,
    "<=": np.less_equal, ">": np.greater, ">=": np.greater_equal,
}


@dataclass
class Answer:
    """One query's result as arrays.  ``keys`` is None for a scalar query;
    ``values[:, j]`` is the aggregate at select position ``agg_pos[j]``."""

    keys: Optional[np.ndarray]
    values: np.ndarray
    agg_pos: List[int]


def _bfloat16():
    import ml_dtypes

    return ml_dtypes.bfloat16


class _Rows:
    """The joined (or single-table) rows, column by column, gathered lazily."""

    def __init__(self, q: Dict[str, Any], tables: Tables, params: Dict[str, Any]):
        self.alias = {(a or t): t for t, a in q["from"]}
        self.single = len(q["from"]) == 1
        masks = {a: None for a in self.alias}
        for col, op, rhs in q.get("where", []):
            a, c = self._split(col)
            arr = tables[self.alias[a]][c]
            val = params[rhs[1:]] if isinstance(rhs, str) and rhs.startswith(":") else rhs
            m = _CMP[op](arr, np.asarray(val).astype(arr.dtype))
            masks[a] = m if masks[a] is None else masks[a] & m
        self.rows = {
            a: (np.flatnonzero(m) if m is not None else None) for a, m in masks.items()
        }
        self.tables = tables
        if q.get("join"):
            (la, lc), (ra, rc) = (self._split(c) for c in q["join"])
            lk, rk = self._col(la, lc), self._col(ra, rc)
            # sort the smaller side, expand the larger one over its matches
            if len(lk) < len(rk):
                (la, lk), (ra, rk) = (ra, rk), (la, lk)
            order = np.argsort(rk, kind="stable")
            sk = rk[order]
            lo = np.searchsorted(sk, lk, side="left")
            hi = np.searchsorted(sk, lk, side="right")
            counts = hi - lo
            probe = np.repeat(np.arange(len(lk)), counts)
            starts = np.repeat(lo - np.concatenate(([0], np.cumsum(counts)[:-1])), counts)
            build = order[starts + np.arange(len(probe))]
            self.rows = {la: self._take(la, probe), ra: self._take(ra, build)}

    def _split(self, col: str):
        if "." in col:
            a, c = col.split(".", 1)
            return a, c
        if not self.single:
            raise ValueError(f"bare column {col!r} over two tables")
        return next(iter(self.alias)), col

    def _take(self, a: str, idx: np.ndarray) -> np.ndarray:
        base = self.rows[a]
        return idx if base is None else base[idx]

    def _col(self, a: str, c: str) -> np.ndarray:
        arr = self.tables[self.alias[a]][c]
        idx = self.rows[a]
        return arr if idx is None else arr[idx]

    def eval(self, expr: str, precision: str) -> np.ndarray:
        names: Dict[str, np.ndarray] = {}

        def bind(a: str, c: str) -> str:
            v = f"{a}__{c}"
            if v not in names:
                arr = self._col(a, c)
                if arr.dtype.kind == "f":
                    arr = arr.astype(_bfloat16() if precision == "bfloat16" else np.float64)
                else:
                    arr = arr.astype(np.int64)
                names[v] = arr
            return v

        text = _COL.sub(lambda m: bind(m.group(1), m.group(2)), expr)
        if self.single:
            a = next(iter(self.alias))
            text = re.sub(
                r"(?<![\w.])([A-Za-z_]\w*)(?![\w.(])",
                lambda m: m.group(1) if "__" in m.group(1) else bind(a, m.group(1)),
                text,
            )
        out = eval(compile(text, "<query>", "eval"), {"__builtins__": {}}, names)  # noqa: S307
        return np.asarray(out).astype(np.float64)

    def n(self) -> int:
        a, idx = next(iter(self.rows.items()))
        if idx is not None:
            return len(idx)
        return len(next(iter(self.tables[self.alias[a]].values())))


def evaluate(q: Dict[str, Any], tables: Tables, params: Dict[str, Any],
             precision: str = "float64") -> Answer:
    rows = _Rows(q, tables, params)
    agg_pos = [i for i, it in enumerate(q["select"]) if is_agg(it)]
    kpos = key_position(q)
    if kpos is None:
        (agg, expr), = (q["select"][i] for i in agg_pos)
        if agg == "count":
            v = float(rows.n())
        else:
            x = rows.eval(expr, precision)
            v = float(x.sum()) if agg == "sum" else float(x.mean()) if agg == "avg" else \
                float(x.min()) if agg == "min" else float(x.max())
        return Answer(None, np.array([[v]]), agg_pos)
    a, c = rows._split(q["group_by"])
    key = rows._col(a, c).astype(np.int64)
    uniq = None
    if len(key) and key.min() >= 0 and key.max() < (1 << 27):
        inv, n_keys = key, int(key.max()) + 1
    else:
        uniq, inv = np.unique(key, return_inverse=True)
        n_keys = len(uniq)
    count = np.bincount(inv, minlength=n_keys)
    present = np.flatnonzero(count)
    cols = []
    for i in agg_pos:
        agg, expr = q["select"][i]
        if agg == "count":
            cols.append(count[present].astype(np.float64))
            continue
        x = rows.eval(expr, precision)
        if agg in ("sum", "avg"):
            s = np.bincount(inv, weights=x, minlength=n_keys)[present]
            cols.append(s if agg == "sum" else s / count[present])
        else:
            acc = np.full(n_keys, np.inf if agg == "min" else -np.inf)
            (np.minimum if agg == "min" else np.maximum).at(acc, inv, x)
            cols.append(acc[present])
    keys = present if uniq is None else uniq[present]
    return Answer(keys.astype(np.int64), np.stack(cols, axis=1), agg_pos)


def returned_rows(q: Dict[str, Any], ans: Answer) -> Answer:
    """``ans`` cut to what a query with ORDER BY ... LIMIT returns: the rows
    in order, at most ``limit`` of them.  Used where an answer computed in
    full stands in for the engine's."""
    if ans.keys is None or not q.get("order_by"):
        return ans
    pos, direction = q["order_by"]
    j = ans.agg_pos.index(pos)
    order = np.argsort(ans.values[:, j], kind="stable")
    if direction.lower() == "desc":
        order = order[::-1]
    if q.get("limit") is not None:
        order = order[: int(q["limit"])]
    return Answer(ans.keys[order], ans.values[order], ans.agg_pos)
