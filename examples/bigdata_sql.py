# End-to-end Big-Data analytics driver (the paper's application class):
# a multi-query session over synthetic web logs through the unified query
# engine — one Session, both frontends (SQL *and* MapReduce), the
# cost-based planner choosing execution strategies per query (EXPLAIN
# shows estimates vs. choices), a shared plan cache, automatic reformatting
# (§III-C1), distribution optimization across queries (§III-A4) and
# fault-tolerant chunked execution (§III-A3) over the row space.
#
# Run:  PYTHONPATH=src python examples/bigdata_sql.py [--rows 2000000]
#       [--planner cost|none] [--explain]
import argparse
import time

import numpy as np

from repro import MapReduceSpec, Session
from repro.compile_cache import use_compile_cache
from repro.sched.fault_tolerant import HybridFaultTolerantScheduler, verify_coverage


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=500_000)
    ap.add_argument("--planner", choices=["cost", "none"], default="cost")
    ap.add_argument("--explain", action="store_true", help="print full EXPLAIN per query")
    args = ap.parse_args()
    use_compile_cache()

    rng = np.random.default_rng(0)
    n = args.rows
    n_servers = 200
    s = Session(n_parts=8, planner=args.planner, expected_runs=12)
    s.register(
        "logs",
        url=np.array([f"http://s{u % 97}.com/p{u}" for u in rng.zipf(1.3, n) % 3000], dtype=object),
        status=rng.choice([200, 200, 200, 304, 404, 500], n).astype(np.int32),
        latency=rng.gamma(2.0, 30.0, n).astype(np.float32),
        bytes=rng.integers(100, 1 << 20, n).astype(np.int32),
        server_id=rng.integers(0, n_servers, n).astype(np.int32),
    )
    # dimension table: unique server ids (the planner picks the cheap
    # unique-lookup join lowering for this side)
    s.register(
        "servers",
        id=np.arange(n_servers, dtype=np.int32),
        region=rng.integers(0, 16, n_servers).astype(np.int32),
    )
    # each server has two mirror rows — duplicate build keys force the
    # expansion join lowering
    s.register(
        "mirrors",
        id=np.repeat(np.arange(n_servers, dtype=np.int32), 2),
        host=rng.integers(0, 1000, 2 * n_servers).astype(np.int32),
    )

    queries = [
        # star-schema aggregate: GROUP BY over a two-table join — the
        # planner picks the unique-lookup join lowering for the dim table
        "SELECT s.region, COUNT(s.region), SUM(l.latency) FROM logs l, servers s "
        "WHERE l.server_id = s.id GROUP BY s.region",
        # duplicate-key join (fan-out 2, expansion lowering) + probe filter
        "SELECT l.url, m.host FROM logs l, mirrors m "
        "WHERE l.server_id = m.id AND l.status = 500",
        "SELECT url, COUNT(url) FROM logs GROUP BY url",
        "SELECT status, COUNT(status) FROM logs GROUP BY status",
        "SELECT status, SUM(latency) FROM logs GROUP BY status",
        "SELECT url FROM logs WHERE status = 500",
        "SELECT SUM(bytes) FROM logs WHERE status = 200",
        # top-k (ORDER BY/LIMIT) — the planner-relevant serving shape
        "SELECT url, COUNT(url) AS c FROM logs GROUP BY url ORDER BY c DESC LIMIT 5",
        # repeat the url-count query: identical (program, stats epoch) must
        # hit the plan cache on a cost-planned session
        "SELECT url, COUNT(url) FROM logs GROUP BY url",
    ]

    print(f"{n} log rows; running {len(queries)} SQL queries + 2 MapReduce jobs "
          f"through the single IR (planner={args.planner})\n")
    t_all = time.perf_counter()

    def show(label: str, r) -> None:
        key = next(iter(r.results))
        val = r.results[key]
        head = val[:2] if isinstance(val, list) else val
        print(f"  [{r.elapsed_s*1e3:7.1f} ms] {label}\n            -> {head}")
        if r.decision is not None:
            c = r.decision.chosen
            pf = f"{c.partition_field[0]}.{c.partition_field[1]}" if c.partition_field else "-"
            hit = "cache HIT" if r.cache_hit else "cache MISS"
            jm = f" join={c.join_method}" if c.join_method else ""
            print(f"            plan: order={c.order} agg={c.agg_method} parallel={c.parallel} "
                  f"partition={pf}{jm} ({hit})")
            if args.explain and r.explain:
                print("\n".join("            " + l for l in r.explain.splitlines()))

    for q in queries:
        show(q, s.sql(q))

    # --- MapReduce jobs through the SAME engine + planner + plan cache ------
    # the url-count job is logically identical to the SQL url-count query
    # above, so on a cost-planned session it is a plan-cache HIT
    for spec in (MapReduceSpec.count("logs", "url"),
                 MapReduceSpec.aggregate("logs", "status", "latency", "max")):
        show(f"MR {spec.name}({spec.table}.{spec.key_field})", s.mapreduce(spec))

    print(f"\nsession total: {(time.perf_counter()-t_all)*1e3:.1f} ms")
    if args.planner == "cost":
        print(f"plan cache: {s.cache_stats()}")
        print("\n" + s.explain(MapReduceSpec.count("logs", "url")))

    # --- the raw pipeline underneath (one low-level snippet) ----------------
    # distribution optimization across adjacent aggregates (§III-A4): the
    # two status group-by queries partition both on logs.status
    from repro import sql_to_forelem
    from repro.core.distribution import optimize_distribution, partition_conflicts
    from repro.core.ir import Program
    from repro.core.transforms import orthogonalize, iteration_space_expansion
    from dataclasses import replace

    schemas = s.schemas()
    p1 = sql_to_forelem(queries[3], schemas)
    p2 = sql_to_forelem(queries[4], schemas)
    combined = Program(p1.tables, p1.body + p2.body, ("R", "R2"), (), "session")
    body = list(combined.body)
    body[3] = replace(body[3], body=(replace(body[3].body[0], result="R2"),))
    combined = combined.with_body(body)
    c = orthogonalize(combined, "logs", "status", 8, which=[0])
    c = orthogonalize(c, "logs", "status", 8, partvar="k2", valvar="l2", which=[0])
    c = iteration_space_expansion(c)
    print("\npartitioning conflicts before distribution optimization:", len(partition_conflicts(c)))
    c2, report = optimize_distribution(c, db=s.db)
    print("after reorder+fusion:", report)

    # --- fault-tolerant chunked execution over the row space (§III-A3) ------
    sched = HybridFaultTolerantScheduler(total_iters=64, n_workers=8, iter_cost=0.02,
                                         checkpoint_period=0.5)
    res = sched.run(failures={3: 0.3})
    assert verify_coverage(res, 64)
    print(f"\nchunked execution with 1 injected node failure: {res.summary()}")


if __name__ == "__main__":
    main()
