# One differential matrix over the engine's execution paths: every query
# below runs on every path that can run it, on the same database, and each
# answer must equal the reference interpreter's.  Every case also checks,
# from what the plan or the metrics registry exposes, that it took the path
# it names — so no two path values silently run the same code.  A change
# that collapses two paths into one runs against this matrix.
#
# shard_map is not a path value here: it needs a multi-device mesh, and
# test_mesh_placement.py runs it on four host devices.
import numpy as np
import pytest

from ab_tables import CORE_QUERIES, SCHEMAS, answer, make_db, ref_rows
from repro.backends import CodegenChoices, PartitionedChoices, PartitionedPlan, get_backend
from repro.backends.jax_vec import Plan
from repro.core import OptimizeOptions, optimize
from repro.engine import QueryServer, Session
from repro.frontends.mapreduce import MapReduceSpec, mapreduce_to_forelem
from repro.frontends.sql import sql_to_forelem

SEED = 0

QUERIES = dict(
    zip(
        ("join", "join_where", "join_count", "join_sum", "join_count_sum",
         "join_min_max", "join_sum_expr"),
        CORE_QUERIES,
    ),
    # the benchmark's traffic (bench/traffic/) on the A/B tables: the BDB
    # aggregation as SQL and as the MapReduce job it alternates with, TPC-H
    # Q6 and the Q15 revenue GROUP BY, and BDB Q3A
    bdb_agg="SELECT f, SUM(w) FROM A GROUP BY f",
    bdb_agg_mr=MapReduceSpec.aggregate("A", "f", "w"),
    q6="SELECT SUM(f * w) FROM A WHERE b_id >= 2 AND b_id < 9 AND f >= 1 AND f <= 4 AND w < 30",
    q15="SELECT b_id, SUM(w * (1 - f)) FROM A WHERE w >= -20 AND w < 30 GROUP BY b_id",
    q3a=(
        "SELECT a.f, SUM(a.w), SUM(b.v) FROM B b, A a "
        "WHERE b.id = a.b_id AND a.w >= -20 AND a.w <= 30 "
        "GROUP BY a.f ORDER BY SUM(a.w) DESC LIMIT 1"
    ),
)

AGG_METHODS = ("dense", "onehot", "sort", "kernel")
PATHS = (
    [f"jax-{m}" for m in AGG_METHODS]
    + ["vmap-dense", "vmap-kernel"]
    + ["partitioned-k1", "partitioned-k3", "partitioned-k8", "pool-k3"]
    + ["session-cost", "server"]
)

# Pairs left out by design, where the path named would run another path's
# code:
# - a query with no aggregate runs the same code under every agg_method, so
#   only 'dense' runs it;
# - 'onehot' evaluates only sums: join_min_max falls back to 'dense' for
#   both of its aggregates (test_kernels' test_onehot_min_fallback_is_loud);
# - optimize partitions a loop for vmap only by an unfiltered GROUP BY key
#   of the loop's own table; the projections, the scalar q6, the filtered
#   q15 and q3a, and the GROUP BYs keyed on the join's build side stay
#   sequential at n_parts=4.
NO_AGGREGATE = {"join", "join_where"}
VMAP_QUERIES = {"join_count", "join_sum", "join_sum_expr", "bdb_agg", "bdb_agg_mr"}


def _runs(path, query):
    if path.startswith("vmap-"):
        return query in VMAP_QUERIES
    if path.startswith("jax-") and path != "jax-dense":
        return query not in NO_AGGREGATE and (path, query) != ("jax-onehot", "join_min_max")
    return True


PAIRS = [
    pytest.param(path, query, id=f"{path}-{query}")
    for path in PATHS
    for query in QUERIES
    if _runs(path, query)
]


def _db():
    return make_db(np.random.default_rng(SEED))


def _program(query):
    if isinstance(query, MapReduceSpec):
        return mapreduce_to_forelem(query, SCHEMAS[query.table])
    return sql_to_forelem(query, SCHEMAS)


def _submit(front, query):
    if isinstance(front, QueryServer):
        return front.submit(query)
    return front.mapreduce(query) if isinstance(query, MapReduceSpec) else front.sql(query)


@pytest.fixture(scope="module")
def server():
    # one server for all of its cases: they share its plan cache, chunk pool
    # and metrics registry, as tenants of a deployed server do
    srv = QueryServer(_db(), backend="partitioned")
    yield srv
    srv.close()


def _check_lowering(lowering, method, parallel):
    assert lowering.choices.agg_method == method
    assert lowering.choices.parallel == parallel
    # every aggregate ran under the method named, none fell back to 'dense'
    assert lowering.method_notes == []


def _check_dispatch(plan, k, pool):
    assert isinstance(plan, PartitionedPlan)
    assert plan.k == k
    assert plan.choices.async_dispatch is pool
    log = plan.dispatch_log
    assert log and all(0 <= d.partition < k for d in log)
    # the serial _dispatch never waits on a chunk (ready_ms stays 0); the
    # _dispatch_pool workers each wait on their own
    if pool:
        assert len(log) > 1 and all(d.ready_ms > 0 for d in log)
    else:
        assert all(d.ready_ms == 0.0 for d in log)


def _run(path, query, request):
    p = _program(query)
    if path.startswith("jax-"):
        method = path.split("-")[1]
        plan = Plan(p, _db(), CodegenChoices(agg_method=method))
        got = plan.run()
        _check_lowering(plan.lowering, method, "none")
        return got
    if path.startswith("vmap-"):
        method = path.split("-")[1]
        res = optimize(
            p, _db(), OptimizeOptions(n_parts=4, agg_method=method, parallel_exec="vmap")
        )
        got = res.plan.run()
        _check_lowering(res.plan.lowering, method, "vmap")
        assert res.plan.lowering.spec.n_parts == 4
        return got
    if path.startswith(("partitioned-", "pool-")):
        k = int(path.split("-k")[1])
        pool = path.startswith("pool-")
        # n_workers pinned: the automatic count falls to one worker, and so
        # to the serial loop, on a one-core host
        choices = PartitionedChoices(
            n_partitions=k, async_dispatch=pool, n_workers=3 if pool else 0
        )
        plan = get_backend("partitioned").compile(p, _db(), choices)
        got = plan.run()
        _check_dispatch(plan, k, pool)
        return got
    if path == "session-cost":
        s = Session(_db(), planner="cost")
        qr = _submit(s, query)
        chosen = qr.decision.chosen
        assert isinstance(qr.plan, Plan)
        assert qr.plan.lowering.choices.agg_method == chosen.agg_method
        assert s.metrics_registry.counter("plan_cache.miss") == 1
        return qr.results
    srv = request.getfixturevalue("server")
    before = srv.metrics.counter("chunks.dispatched")
    qr = _submit(srv, query)
    # the chunks ran on the server's SharedChunkPool
    assert isinstance(qr.plan, PartitionedPlan)
    assert qr.plan.chunk_executor is srv.pool
    dispatched = srv.metrics.counter("chunks.dispatched") - before
    assert dispatched == len(qr.plan.dispatch_log) > 0
    return qr.results


@pytest.mark.parametrize("path,query", PAIRS)
def test_every_path_matches_reference(path, query, request):
    q = QUERIES[query]
    want = ref_rows(_program(q), _db())
    assert answer(_run(path, q, request)) == want
