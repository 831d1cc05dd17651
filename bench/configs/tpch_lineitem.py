"""Generator of TPC-H ``lineitem`` (specification sec. 4.2.3) from a seed,
with numpy on the host, where the engine's tables live."""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from bench.datagen import draw, ints

STARTDATE = 8035     # 1992-01-01
ENDDATE = 10591      # 1998-12-31
CURRENTDATE = 9298   # 1995-06-17


def line_counts(orders: int, lo: int, hi: int) -> np.ndarray:
    """A fixed multiset of per-order line counts: each of lo..hi equally
    often, the remainder as the middle count, so the row total is exact."""
    per = orders // (hi - lo + 1)
    counts = np.repeat(np.arange(lo, hi + 1, dtype=np.int32), per)
    rest = orders - len(counts)
    return np.concatenate([counts, np.full(rest, (lo + hi) // 2, np.int32)])


def generate(cfg: Dict[str, Any], seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    n_orders = int(cfg["orders"])
    lo, hi = cfg["lines_per_order"]
    s, parts = int(cfg["suppliers"]), int(cfg["parts"])
    base = line_counts(n_orders, lo, hi)
    n = int(base.sum())
    c = draw(seed, {
        "counts": lambda rng: rng.permutation(base),
        "orderdate": ints(STARTDATE, ENDDATE - 151 + 1, n_orders),
        "partkey": ints(1, parts + 1, n),
        "supp_i": ints(0, 4, n),
        "quantity": ints(1, 51, n),
        "ship_days": ints(1, 122, n),
        "receipt_days": ints(1, 31, n),
        "returned": ints(0, 2, n),
        "discount": ints(0, 11, n),
        "tax": ints(0, 9, n),
        "commit_days": ints(30, 91, n),
        "l_shipinstruct": ints(0, 4, n),
        "l_shipmode": ints(0, 7, n),
        "l_comment": ints(0, 1 << 24, n),
    })
    counts = c.pop("counts")
    order = np.repeat(np.arange(n_orders, dtype=np.int32), counts)
    starts = (np.cumsum(counts) - counts).astype(np.int32)
    orderdate = c.pop("orderdate")[order]
    partkey, i = c.pop("partkey"), c.pop("supp_i")
    quantity = c.pop("quantity").astype(np.float32)
    retail = (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)).astype(np.float32) / np.float32(100)
    shipdate = orderdate + c.pop("ship_days")
    receiptdate = shipdate + c.pop("receipt_days")
    returned = c.pop("returned") * 2  # R or A
    return {"lineitem": {
        "l_orderkey": (order // 8) * 32 + order % 8 + 1,
        "l_partkey": partkey,
        "l_suppkey": (partkey + i * (s // 4 + (partkey - 1) // s)) % s + 1,
        "l_linenumber": np.arange(n, dtype=np.int32) - starts[order] + 1,
        "l_quantity": quantity,
        "l_extendedprice": quantity * retail,
        "l_discount": c.pop("discount").astype(np.float32) / np.float32(100),
        "l_tax": c.pop("tax").astype(np.float32) / np.float32(100),
        "l_returnflag": np.where(receiptdate <= CURRENTDATE, returned, 1).astype(np.int32),
        "l_linestatus": (shipdate > CURRENTDATE).astype(np.int32),
        "l_shipdate": shipdate,
        "l_commitdate": orderdate + c.pop("commit_days"),
        "l_receiptdate": receiptdate,
        **c,
    }}
