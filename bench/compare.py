"""The comparison that decides ``correct``: the engine's answer to one
request against the reference's answer to the same request.

Two numbers per answer:

- ``bad_keys``: group keys the engine returned that the reference lacks,
  that it left out, or returned twice, rows beyond the LIMIT or missing
  from it, and rows out of ORDER BY order.  Exact: the limit is 0.
- ``rel_err``: the widest relative gap between a value the engine returned
  and the reference's value for the same key, over every aggregate.  Under
  ORDER BY ... LIMIT k it also holds how far a returned row's ordering value
  lies below the reference's k-th best (ties or rounding may pick another
  row of the same value; a worse row is an error).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.query import key_position
from bench.reference import Answer

TINY = 1e-30


def engine_answer(q: Dict[str, Any], results: Dict[str, Any]) -> Answer:
    """The engine's ``QueryResult.results`` as an ``Answer`` in select order."""
    agg_pos = [i for i, it in enumerate(q["select"]) if not isinstance(it, str)]
    kpos = key_position(q)
    if kpos is None:
        return Answer(None, np.array([[float(results["scalar"])]]), agg_pos)
    rows: List[Tuple] = results["R"]
    if not rows:
        return Answer(np.zeros(0, np.int64), np.zeros((0, len(agg_pos))), agg_pos)
    arr = np.array(rows, dtype=np.float64)
    return Answer(arr[:, kpos].astype(np.int64), arr[:, agg_pos], agg_pos)


def _rel(got: np.ndarray, want: np.ndarray) -> float:
    if got.size == 0:
        return 0.0
    err = np.abs(got - want) / np.maximum(np.abs(want), TINY)
    err = np.where(np.isnan(got) != np.isnan(want), np.inf, np.nan_to_num(err, nan=0.0))
    return float(err.max())


def compare(q: Dict[str, Any], got: Answer, ref: Answer) -> Dict[str, float]:
    if ref.keys is None:
        return {"rel_err": _rel(got.values[:, 0], ref.values[:, 0]), "bad_keys": 0.0}
    bad = 0
    gk = got.keys
    uk, first = np.unique(gk, return_index=True)
    bad += len(gk) - len(uk)                      # duplicate keys
    gv = got.values[first]
    pos = np.searchsorted(ref.keys, uk)
    pos_c = np.minimum(pos, max(len(ref.keys) - 1, 0))
    found = (pos < len(ref.keys)) & (ref.keys[pos_c] == uk) if len(ref.keys) else \
        np.zeros(len(uk), bool)
    bad += int((~found).sum())                    # keys the reference lacks
    rv = ref.values[pos_c[found]]
    rel = _rel(gv[found], rv)
    limit = q.get("limit")
    if limit is None:
        bad += len(ref.keys) - int(found.sum())   # keys left out
        return {"rel_err": rel, "bad_keys": float(bad)}
    n_want = min(int(limit), len(ref.keys))
    bad += abs(len(gk) - n_want)
    if q.get("order_by") and n_want:
        opos, direction = q["order_by"]
        j = got.agg_pos.index(opos)
        desc = direction.lower() == "desc"
        order_vals = ref.values[:, j]
        kth = np.sort(order_vals)[::-1][n_want - 1] if desc else np.sort(order_vals)[n_want - 1]
        if found.any():
            mine = rv[:, j]
            gap = (kth - mine) if desc else (mine - kth)
            rel = max(rel, float(np.max(np.maximum(gap, 0.0)) / max(abs(kth), TINY)))
        seq = got.values[:, j]
        steps = np.diff(seq)
        bad += int(((steps > 0) if desc else (steps < 0)).sum())
    return {"rel_err": rel, "bad_keys": float(bad)}


def worst(readings: List[Dict[str, float]]) -> Optional[Dict[str, float]]:
    """The widest of each number over many answers."""
    if not readings:
        return None
    return {k: max(r[k] for r in readings) for k in readings[0]}
