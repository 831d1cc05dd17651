"""The one general generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<mix>.json``) holds:

- ``entry``: ``{"kind": "session" | "server", "options": {...}}``, the
  engine entry the requests go through and its constructor arguments;
  ``"mesh": true`` among them stands for a mesh over the cell's chips.
- ``loop``: ``{"kind": "closed"}`` (one client, each query sent when the
  last returned) or
  ``{"kind": "open", "rate_qps": r, "schedule_seed": s, "submitters": n,
  "tenants": [[name, share], ...]}``.
- ``templates``: ``[{"name", "api": "sql" | "mapreduce", "query": {...},
  "share", "params": [{"names": [...], "dtype": "int32", "values": [[...], ...]}]}]``.
  Each parameter group draws one row of ``values`` per request.
- ``warmup``: calls per template (and per tenant) before the window.
- ``check``: ``{"rel_err_limit": x, "bad_keys_limit": 0}``; every
  finished answer is compared.

A closed loop cycles through the templates in order.  An open loop's
arrivals and the template of each request are drawn once from the traffic
file's ``loop.schedule_seed``, the same for every run: the gaps are the
quantiles of an exponential at the stated rate (Poisson in shape) in that
seed's order, and each template appears its share of the requests.  The
run's seed draws the tenants (each its share) and the parameters.  With
some tens of requests to a window, the order of the gaps decides how long
the queue grows, and so the tail: drawn per run it would move the tail by
a fifth from seed to seed.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Request:
    idx: int
    template: Dict[str, Any]
    params: Dict[str, Any]
    tenant: str = "default"
    due_s: Optional[float] = None   # offset from the window's start (open loop)


def draw_params(template: Dict[str, Any], rng: np.random.Generator) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for group in template.get("params", []):
        row = group["values"][int(rng.integers(len(group["values"])))]
        for name, v in zip(group["names"], row):
            out[name] = np.asarray(v).astype(group["dtype"])[()]
    return out


def closed_request(traffic: Dict[str, Any], seed: int, i: int) -> Request:
    """The i-th request of a closed loop; a stream of its own per index."""
    templates = traffic["templates"]
    t = templates[i % len(templates)]
    return Request(i, t, draw_params(t, np.random.default_rng([seed, i])))


def warmup_requests(traffic: Dict[str, Any]) -> List[Request]:
    """Every template (under every tenant, in an open loop) ``warmup`` times,
    with parameters from a stream no window uses."""
    tenants = [n for n, _ in traffic["loop"].get("tenants", [["default", 1.0]])]
    out = []
    rng = np.random.default_rng([0, 1 << 40])
    for _ in range(traffic.get("warmup", 3)):
        for t in traffic["templates"]:
            for tenant in tenants:
                out.append(Request(len(out), t, draw_params(t, rng), tenant))
    return out


def _counts(shares: List[float], n: int) -> List[int]:
    c = [int(round(s * n)) for s in shares]
    c[0] += n - sum(c)
    return c


def open_schedule(traffic: Dict[str, Any], seed: int, seconds: float,
                  rate_qps: Optional[float] = None) -> List[Request]:
    loop = traffic["loop"]
    rate = float(rate_qps if rate_qps is not None else loop["rate_qps"])
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(loop["schedule_seed"])
    # n - 1 gaps, the quantiles of an exponential, scaled to end the last
    # request at (n - 1) / n of the window; the first is due at 0
    k = max(n - 1, 1)
    gaps = fixed.permutation(-np.log1p(-(np.arange(k) + 0.5) / k))
    due = np.concatenate(([0.0], np.cumsum(gaps)))[:n] * (seconds * (n - 1) / n / gaps.sum())
    templates = traffic["templates"]
    t_idx = fixed.permutation(np.repeat(np.arange(len(templates)),
                                        _counts([t["share"] for t in templates], n)))
    rng = np.random.default_rng(seed)
    tenants = loop["tenants"]
    ten_idx = rng.permutation(np.repeat(np.arange(len(tenants)),
                                        _counts([s for _, s in tenants], n)))
    out = []
    for i in range(n):
        t = templates[int(t_idx[i])]
        out.append(Request(i, t, draw_params(t, rng), tenants[int(ten_idx[i])][0], float(due[i])))
    return out

