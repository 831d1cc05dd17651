"""Smoke run of the query engine's main path on one TPU chip.

Generates the ``examples/bigdata_sql.py`` star schema from ``--seed`` at
``--rows`` (fact table ``logs``; dimension tables ``servers`` and
``mirrors``), runs it through ``Session.sql``, ``Session.mapreduce`` and
``QueryServer.submit`` on the ``jax`` (cost planner) and ``partitioned``
backends, and checks every answer against a plain numpy oracle.  Prints each
query's plan, cold (compile) and warm milliseconds and the device's peak
memory.  The last line of stdout is one JSON object naming the device; it is
printed only when every check passed.

    python chip_smoke.py                  # one chip, 2^25 rows
    python chip_smoke.py --chips 4        # only the shard_map mesh phase
    JAX_PLATFORMS=cpu REPRO_PALLAS=1 python chip_smoke.py --rows 200000
        # CPU rehearsal: runs every phase, then fails on the device checks

The exit code is 0 only on a TPU, with every answer right, the compiled
Pallas kernel chosen on each backend and no planner fallback.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro import MapReduceSpec, QueryServer, Session  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.kernels.segreduce.ops import pallas_mode  # noqa: E402

N_SERVERS = 200
N_REGIONS = 16
N_URLS = 3000
N_PARTITIONS = 8  # the partitioned backend's K, on the chip and in the server
# f32 sums: the kernel adds 128 lane partials per key, the oracle sums in
# float64 — over 2^24 values of ~60 the f32 rounding stays well inside this
F32_SUM_RTOL = 1e-4


# ---------------------------------------------------------------------------
# data and the numpy oracle
# ---------------------------------------------------------------------------


def make_tables(rows: int, seed: int) -> Dict[str, Dict[str, np.ndarray]]:
    """The examples/bigdata_sql.py star schema, with integer url ids and a
    small ``kb`` column in place of the url strings and byte counts."""
    rng = np.random.default_rng(seed)
    logs = {
        "url_id": (rng.zipf(1.3, rows) % N_URLS).astype(np.int32),
        "status": rng.choice(np.array([200, 200, 200, 304, 404, 500], np.int32), rows),
        "latency": rng.gamma(2.0, 30.0, rows).astype(np.float32),
        "kb": rng.integers(0, 64, rows, dtype=np.int32),
        "server_id": rng.integers(0, N_SERVERS, rows, dtype=np.int32),
    }
    servers = {
        "id": np.arange(N_SERVERS, dtype=np.int32),
        "region": rng.integers(0, N_REGIONS, N_SERVERS).astype(np.int32),
    }
    # two mirror rows per server: duplicate build keys (expansion join)
    mirrors = {
        "id": np.repeat(np.arange(N_SERVERS, dtype=np.int32), 2),
        "host": rng.integers(0, 1000, 2 * N_SERVERS).astype(np.int32),
    }
    return {"logs": logs, "servers": servers, "mirrors": mirrors}


def _int_bincount(keys: np.ndarray, weights: Optional[np.ndarray], n: int) -> np.ndarray:
    if weights is None:
        return np.bincount(keys, minlength=n).astype(np.int64)
    # float64 sums of int32 values are exact below 2^53
    return np.bincount(keys, weights=weights, minlength=n).astype(np.int64)


def oracle(t: Dict[str, Dict[str, np.ndarray]]) -> Dict[str, Any]:
    logs, servers, mirrors = t["logs"], t["servers"], t["mirrors"]
    out: Dict[str, Any] = {}
    region = servers["region"][logs["server_id"]]
    cnt = _int_bincount(region, None, N_REGIONS)
    kb = _int_bincount(region, logs["kb"], N_REGIONS)
    out["star"] = {r: (int(cnt[r]), int(kb[r])) for r in range(N_REGIONS) if cnt[r]}

    sel = (logs["status"] == 500) & (logs["latency"] > np.float32(200.0))
    hosts = mirrors["host"].reshape(N_SERVERS, 2)[logs["server_id"][sel]]
    urls = logs["url_id"][sel]
    out["mirrors"] = Counter(
        zip(np.repeat(urls, 2).tolist(), hosts.reshape(-1).tolist())
    )

    ucnt = _int_bincount(logs["url_id"], None, N_URLS)
    out["count_url"] = {u: int(ucnt[u]) for u in range(N_URLS) if ucnt[u]}

    status = {}
    for s in np.unique(logs["status"]):
        m = logs["status"] == s
        lat = logs["latency"][m]
        status[int(s)] = (
            float(lat.astype(np.float64).sum()),
            float(lat.min()),
            float(lat.max()),
            int(logs["kb"][m].astype(np.int64).sum()),
        )
    out["status_fused"] = status
    out["scalar_kb"] = int(logs["kb"][logs["status"] == 200].astype(np.int64).sum())

    sid = logs["server_id"]
    scnt = _int_bincount(sid, None, N_SERVERS)
    skb = _int_bincount(sid, logs["kb"], N_SERVERS)
    smax = np.full(N_SERVERS, -np.inf, np.float32)
    np.maximum.at(smax, sid, logs["latency"])
    out["by_server"] = {
        s: (int(scnt[s]), int(skb[s]), float(smax[s])) for s in range(N_SERVERS) if scnt[s]
    }
    return out


# ---------------------------------------------------------------------------
# queries and their checks
# ---------------------------------------------------------------------------


def _rows(r) -> List[tuple]:
    rows = r.rows
    if rows is None:
        raise AssertionError(f"no multiset result R in {list(r.results)}")
    return rows


def check_star(r, want) -> None:
    got = {int(k): (int(c), int(s)) for k, c, s in _rows(r)}
    assert got == want, f"star join: {got} != {want}"


def check_mirrors(r, want) -> None:
    got = Counter((int(u), int(h)) for u, h in _rows(r))
    assert got == want, f"mirrors join: {sum(got.values())} rows, want {sum(want.values())}"


def check_count_url(r, want) -> None:
    got = {int(k): int(c) for k, c in _rows(r)}
    assert got == want, f"COUNT by url_id: {len(got)} groups differ from the oracle's {len(want)}"


def check_status(r, want) -> None:
    got = {int(row[0]): row[1:] for row in _rows(r)}
    assert set(got) == set(want), f"status groups {sorted(got)} != {sorted(want)}"
    for s, (sm, mn, mx, kb) in want.items():
        g_sm, g_mn, g_mx, g_kb = got[s]
        assert abs(float(g_sm) - sm) <= F32_SUM_RTOL * abs(sm), f"SUM(latency)[{s}] {g_sm} vs {sm}"
        assert float(g_mn) == mn and float(g_mx) == mx, f"MIN/MAX(latency)[{s}]"
        assert int(g_kb) == kb, f"SUM(kb)[{s}] {g_kb} vs {kb}"


def check_by_server(r, want) -> None:
    got = {int(k): (int(c), int(kb), float(mx)) for k, c, kb, mx in _rows(r)}
    assert got == want, f"GROUP BY server_id: {len(got)} groups differ from the oracle's"


def check_scalar(r, want) -> None:
    got = int(r.scalar())
    assert got == want, f"SUM(kb) WHERE status = 200: {got} != {want}"


def check_top5(r, want) -> None:
    rows = _rows(r)
    counts = sorted(want.values(), reverse=True)[:5]
    assert len(rows) == 5, f"top-5 returned {len(rows)} rows"
    assert [int(c) for _, c in rows] == counts, f"top-5 counts {rows} vs {counts}"
    for k, c in rows:
        assert want.get(int(k)) == int(c), f"top-5 row ({k}, {c})"


@dataclass(frozen=True)
class Query:
    name: str
    query: Any  # SQL text or MapReduceSpec
    oracle_key: str
    check: Callable[[Any, Any], None]
    count: bool = False    # has a COUNT (an int32 sum of ones)
    int_sum: bool = False  # has an integer SUM


QUERIES = [
    Query(
        "star",
        "SELECT s.region, COUNT(s.region), SUM(l.kb) FROM logs l, servers s "
        "WHERE l.server_id = s.id GROUP BY s.region",
        "star", check_star, count=True, int_sum=True,
    ),
    Query(
        "mirrors",
        "SELECT l.url_id, m.host FROM logs l, mirrors m "
        "WHERE l.server_id = m.id AND l.status = 500 AND l.latency > 200.0",
        "mirrors", check_mirrors,
    ),
    Query(
        "count_url", "SELECT url_id, COUNT(url_id) FROM logs GROUP BY url_id",
        "count_url", check_count_url, count=True,
    ),
    Query(
        "status_fused",
        "SELECT status, SUM(latency), MIN(latency), MAX(latency), SUM(kb) "
        "FROM logs GROUP BY status",
        "status_fused", check_status, int_sum=True,
    ),
    Query("scalar_kb", "SELECT SUM(kb) FROM logs WHERE status = 200", "scalar_kb", check_scalar),
    Query(
        "top5",
        "SELECT url_id, COUNT(url_id) AS c FROM logs GROUP BY url_id ORDER BY c DESC LIMIT 5",
        "count_url", check_top5, count=True,
    ),
    # logically the count_url query: must be served from the plan cache
    Query("mr_count", MapReduceSpec.count("logs", "url_id"), "count_url", check_count_url, count=True),
]
BY_NAME = {q.name: q for q in QUERIES}
# the mesh phase's GROUP BYs: a uniform key and a fused group
MESH_QUERIES = [
    Query(
        "by_server",
        "SELECT server_id, COUNT(server_id), SUM(kb), MAX(latency) FROM logs GROUP BY server_id",
        "by_server", check_by_server, count=True, int_sum=True,
    ),
    BY_NAME["status_fused"],
]


# ---------------------------------------------------------------------------
# running and reporting
# ---------------------------------------------------------------------------


class Failures:
    """Every failed check, printed as it happens; the run fails at the end."""

    def __init__(self) -> None:
        self.items: List[str] = []

    def add(self, msg: str) -> None:
        self.items.append(msg)
        print(f"  FAIL {msg}", flush=True)


def submit(target, q: Query, **kw):
    if isinstance(target, QueryServer):
        return target.submit(q.query, **kw)
    return target.mapreduce(q.query) if isinstance(q.query, MapReduceSpec) else target.sql(q.query)


def plan_str(r) -> str:
    d = r.decision
    if d is None:
        return "no planner decision"
    c = d.chosen
    fused = f" fused={c.fused_aggs}" if c.fused_aggs else ""
    return (
        f"agg_method={c.agg_method} parallel={c.parallel} K={c.n_partitions} "
        f"schedule={c.schedule} join={c.join_method}{fused}"
    )


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e3


def run_session_phase(label: str, sess: Session, want: Dict[str, Any], fails: Failures) -> List[Query]:
    """Every query cold then warm on one session; returns the queries whose
    plan chose the segreduce kernel."""
    print(f"\n== {label} ==", flush=True)
    kernel_queries = []
    for q in QUERIES:
        try:
            r, cold = timed(lambda: submit(sess, q))
            r2, warm = timed(lambda: submit(sess, q))
            q.check(r, want[q.oracle_key])
            q.check(r2, want[q.oracle_key])
        except Exception as e:  # report every query, then fail the run
            fails.add(f"{label}/{q.name}: {type(e).__name__}: {e}")
            continue
        print(
            f"  {q.name:13s} cold {cold:10.1f} ms  warm {warm:9.1f} ms  "
            f"cache_hit={r.cache_hit}  {plan_str(r)}",
            flush=True,
        )
        d = r.decision
        if d is None or d.fallback_reason:
            fails.add(f"{label}/{q.name}: planner fallback: {d and d.fallback_reason}")
        elif d.chosen.agg_method == "kernel":
            kernel_queries.append(q)
        if q.name == "mr_count" and not r.cache_hit:
            fails.add(f"{label}/mr_count: the MapReduce count did not hit the plan cache")
    return kernel_queries


def run_server_phase(tables, want, n_partitions: int, fails: Failures) -> None:
    tenants = ["alice", "bob", "carol", "dave"]
    names = ["count_url", "status_fused", "star", "scalar_kb"]
    print(f"\n== QueryServer: {len(tenants)} tenants at once, backend=partitioned K={n_partitions} ==",
          flush=True)
    with QueryServer(backend="partitioned", n_partitions=n_partitions, admission="block") as srv:
        for t, cols in tables.items():
            srv.register(t, **cols)

        def tenant(name: str, i: int) -> List[str]:
            lines = []
            for j in range(len(names)):
                q = BY_NAME[names[(i + j) % len(names)]]
                r, ms = timed(lambda: submit(srv, q, tenant=name, priority=i % 2))
                q.check(r, want[q.oracle_key])
                lines.append(f"  {name:6s} {q.name:13s} {ms:10.1f} ms  cache_hit={r.cache_hit}")
            return lines

        with ThreadPoolExecutor(max_workers=len(tenants)) as ex:
            futs = [ex.submit(tenant, n, i) for i, n in enumerate(tenants)]
            for n, f in zip(tenants, futs):
                try:
                    print("\n".join(f.result()), flush=True)
                except Exception as e:  # report every tenant, then fail the run
                    fails.add(f"server/{n}: {type(e).__name__}: {e}")
        print(f"  plan cache: {srv.stats()['plan_cache']}", flush=True)


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def register_all(sess: Session, tables) -> Session:
    for t, cols in tables.items():
        sess.register(t, **cols)
    return sess


def same_rows(a: List[tuple], b: List[tuple]) -> bool:
    """Equal rows: integers exactly, floats within the f32-sum tolerance."""
    if len(a) != len(b):
        return False
    for x, y in zip(sorted(a), sorted(b)):
        for u, v in zip(x, y):
            if isinstance(u, float) or isinstance(v, float):
                if abs(u - v) > F32_SUM_RTOL * abs(v):
                    return False
            elif u != v:
                return False
    return True


def mesh_phase(tables, want, fails: Failures) -> None:
    """GROUP BY aggregates under parallel=shard_map on a 4-device mesh,
    against the oracle and the same queries on one device."""
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 4:
        fails.add(f"--chips 4 needs 4 devices, JAX sees {len(devs)}")
        return
    mesh = Mesh(np.array(devs[:4]), ("data",))
    print(f"\n== mesh: {mesh.shape} over devices {[d.id for d in devs[:4]]} ==", flush=True)
    multi = register_all(Session(mesh=mesh, n_parts=4, revalidate="signature"), tables)
    single = register_all(Session(revalidate="signature"), tables)
    for q in MESH_QUERIES:
        name = q.name
        try:
            r, cold = timed(lambda: submit(multi, q))
            _, warm = timed(lambda: submit(multi, q))
            q.check(r, want[q.oracle_key])
            r1 = submit(single, q)
            q.check(r1, want[q.oracle_key])
        except Exception as e:  # report every query, then fail the run
            fails.add(f"mesh/{name}: {type(e).__name__}: {e}")
            continue
        print(f"  {name:13s} cold {cold:10.1f} ms  warm {warm:9.1f} ms  {plan_str(r)}", flush=True)
        print(f"  {'':13s} one device: {plan_str(r1)}", flush=True)
        if r.decision is None or r.decision.chosen.parallel != "shard_map":
            fails.add(f"mesh/{name}: the planner did not choose parallel=shard_map")
        if not same_rows(r.rows, r1.rows):
            fails.add(f"mesh/{name}: 4-device and 1-device results differ")
        # where the program's inputs and outputs live
        cols = r.plan.input_columns()
        compiled = r.plan.fn.lower(cols).compile()
        in_devs = sorted({d.id for s in jax.tree.leaves(compiled.input_shardings) for d in s.device_set})
        out_devs = sorted({d.id for s in jax.tree.leaves(compiled.output_shardings) for d in s.device_set})
        outs = jax.tree.leaves(r.plan.fn(cols))
        held = sorted({d.id for a in outs for d in a.sharding.device_set})
        n_ar = compiled.as_text().count("all-reduce(")
        print(
            f"  {'':13s} input shards on {in_devs}, output shards on {out_devs}, "
            f"result arrays on {held}, all-reduce ops {n_ar}",
            flush=True,
        )
        if len(in_devs) < 4 or len(out_devs) < 4:
            fails.add(f"mesh/{name}: shards on {in_devs} / {out_devs}, not 4 devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard_map mesh phase on four devices")
    args = ap.parse_args()
    if args.rows * 63 >= 2**31:
        ap.error("--rows too large: SUM(kb) totals would pass 2^31 and wrap in int32")

    dev = jax.devices()[0]
    mode = pallas_mode()
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(jax.devices())}",
          flush=True)
    print(f"pallas_mode: {mode}", flush=True)
    if dev.platform != "tpu" and mode != "interpret":
        print("no TPU found; for a CPU rehearsal set REPRO_PALLAS=1 and a small --rows",
              file=sys.stderr)
        return 1
    cache_dir = use_compile_cache()
    cache_events: Counter = Counter()
    jax.monitoring.register_event_listener(
        lambda event, **kw: cache_events.update([event]) if "compilation_cache" in event else None
    )
    print("note: int32 SUM wraps past 2^31 (lint rule sum-overflow); kb < 64 keeps every "
          "exact total here below 2^31, and the wrap itself is not checked", flush=True)

    t0 = time.perf_counter()
    tables = make_tables(args.rows, args.seed)
    want = oracle(tables)
    print(f"data: {args.rows} log rows, seed {args.seed}, "
          f"{sum(a.nbytes for a in tables['logs'].values()) / 2**20:.0f} MiB fact table; "
          f"generated and oracle answered in {time.perf_counter() - t0:.1f} s", flush=True)

    fails = Failures()
    if args.chips == 4:
        mesh_phase(tables, want, fails)
    else:
        for label, sess in (
            ("jax", Session(revalidate="signature")),
            ("partitioned", Session(backend="partitioned", n_partitions=N_PARTITIONS,
                                    revalidate="signature")),
        ):
            kq = run_session_phase(label, register_all(sess, tables), want, fails)
            print(f"  kernel queries: {[q.name for q in kq]}; peak_bytes_in_use={peak_bytes()}",
                  flush=True)
            if not any(q.count for q in kq) or not any(q.int_sum for q in kq):
                fails.add(f"device check: no {label} query with a COUNT and none with an "
                          "integer SUM ran agg_method=kernel")
        run_server_phase(tables, want, N_PARTITIONS, fails)

    print(f"\npeak_bytes_in_use: {peak_bytes()}", flush=True)
    print(f"compile cache: {cache_dir}; events {dict(cache_events)}", flush=True)
    print(f"total: {time.perf_counter() - t0:.1f} s", flush=True)
    if dev.platform != "tpu":
        fails.add(f"device check: platform is {dev.platform!r}, not 'tpu'")
    if mode != "compiled":
        fails.add(f"device check: pallas_mode() is {mode!r}, not 'compiled'")
    if fails.items:
        print(f"{len(fails.items)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
