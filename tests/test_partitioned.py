# Differential tests for the partitioned executor backend
# (backends/partitioned.py): query shapes run over K-way hash/range-
# partitioned data with scheduled chunk dispatch must equal the reference
# interpreter bit-for-bit — unique build keys, schedule policies, filtered
# groups, empty partitions, empty build sides — plus the planner's
# (K, schedule) decision and the shard_map max/min bugfix.  The core query
# shapes on every K (and on every other execution path) are in
# test_paths.py.
import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from ab_tables import CORE_QUERIES, SCHEMAS, make_db, ref_rows
from repro.backends import (
    CodegenChoices,
    PartitionedChoices,
    PartitionedPlan,
    ReferenceInterpreter,
    get_backend,
)
from repro.backends.partitioned import hash_partition
from repro.core import OptimizeOptions, optimize
from repro.data.multiset import Database, Multiset
from repro.engine import Session
from repro.frontends.sql import sql_to_forelem
from repro.planner import DbStats, FieldStats, PlanCache, TableStats, plan_query

KS = (1, 3, 8)


def part_rows(p, db, k, schedule="static", **choice_kw):
    plan = get_backend("partitioned").compile(
        p, db, PartitionedChoices(n_partitions=k, schedule=schedule, **choice_kw)
    )
    return sorted(plan.run()["R"])


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("sql", CORE_QUERIES[:2] + CORE_QUERIES[4:5])
def test_unique_build_matches_reference(rng, sql, k):
    db = make_db(rng, dup_build=False)
    p = sql_to_forelem(sql, SCHEMAS)
    assert part_rows(p, db, k) == ref_rows(p, db)


@pytest.mark.parametrize("schedule", ("static", "fixed", "guided"))
@pytest.mark.parametrize("k", KS)
def test_schedule_policies_match_reference(rng, schedule, k):
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[4], SCHEMAS)
    assert part_rows(p, db, k, schedule) == ref_rows(p, db)


@pytest.mark.parametrize("agg", ["MIN", "MAX", "SUM"])
@pytest.mark.parametrize("k", KS)
def test_filtered_minmax_single_table(rng, agg, k):
    # all-negative values + filter: partial-merge must preserve op identities
    kk = rng.integers(0, 8, 400).astype(np.int32)
    v = rng.integers(-100, -1, 400).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=kk, v=v))
    p = sql_to_forelem(f"SELECT k, {agg}(v) FROM t WHERE v < -10 GROUP BY k", {"t": ["k", "v"]})
    assert part_rows(p, db, k, "guided") == ref_rows(p, db)


def test_filtered_group_emptied_across_partitions(rng):
    # group 3 is emptied by the filter; K=8 over 4 distinct keys also leaves
    # most partitions empty — neither may emit phantom rows
    kk = np.array([0, 0, 1, 1, 2, 3, 3], np.int32)
    v = np.array([5, -7, 9, 2, -4, 100, 100], np.int32)
    db = Database().add(Multiset.from_columns("t", k=kk, v=v))
    p = sql_to_forelem("SELECT k, MIN(v), MAX(v) FROM t WHERE v < 50 GROUP BY k", {"t": ["k", "v"]})
    for k in KS:
        assert part_rows(p, db, k) == [(0, -7, 5), (1, 2, 9), (2, -4, -4)]


@pytest.mark.parametrize("k", KS)
def test_empty_build_side(rng, k):
    A = Multiset.from_columns("A", b_id=rng.integers(0, 5, 20).astype(np.int32),
                              f=rng.integers(0, 4, 20).astype(np.int32),
                              w=rng.integers(-9, 9, 20).astype(np.int32))
    B = Multiset.from_columns("B", id=np.array([], np.int32), g=np.array([], np.int32),
                              v=np.array([], np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    assert part_rows(p, db, k) == [] == ReferenceInterpreter(db).run(p)["R"]


@pytest.mark.parametrize("k", KS)
def test_no_matching_probes(rng, k):
    A = Multiset.from_columns("A", b_id=(100 + rng.integers(0, 5, 20)).astype(np.int32),
                              f=rng.integers(0, 4, 20).astype(np.int32),
                              w=np.zeros(20, np.int32))
    B = Multiset.from_columns("B", id=rng.integers(0, 5, 10).astype(np.int32),
                              g=rng.integers(0, 4, 10).astype(np.int32),
                              v=np.zeros(10, np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    assert part_rows(p, db, k) == [] == ReferenceInterpreter(db).run(p)["R"]


def test_order_by_limit(rng):
    kk = rng.integers(0, 7, 300).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=kk))
    p = sql_to_forelem(
        "SELECT k, COUNT(k) FROM t GROUP BY k ORDER BY COUNT(k) DESC LIMIT 3", {"t": ["k"]}
    )
    counts = sorted(np.unique(kk, return_counts=True)[1].tolist(), reverse=True)[:3]
    for k in KS:
        plan = get_backend("partitioned").compile(p, db, PartitionedChoices(n_partitions=k))
        assert [c for _, c in plan.run()["R"]] == counts


# ---------------------------------------------------------------------------
# backend mechanics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("schedule", ("static", "guided"))
@pytest.mark.parametrize("k", KS)
def test_streaming_row_order_independent_of_partitioning(rng, k, schedule):
    # visible row order of streaming results (joins, filter/project) must
    # not depend on the (K, schedule) choice: it matches the jax backend's
    # probe-row-major emission, so LIMIT without ORDER BY is stable too
    from repro.backends import Plan

    db = make_db(rng)
    for sql in (
        "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id",
        "SELECT a.f, a.w FROM A a WHERE a.w > 0",
    ):
        p = sql_to_forelem(sql, SCHEMAS)
        jax_rows = Plan(p, db).run()["R"]
        plan = get_backend("partitioned").compile(
            p, db, PartitionedChoices(n_partitions=k, schedule=schedule)
        )
        assert plan.run()["R"] == jax_rows  # NOT sorted(): exact order


def test_hash_partition_co_partitions_both_sides():
    vals = np.arange(-50, 50, dtype=np.int64)
    pa, pb = hash_partition(vals, 8), hash_partition(vals.copy(), 8)
    assert (pa == pb).all() and pa.min() >= 0 and pa.max() < 8


def test_chunks_never_cross_partition_boundaries(rng):
    db = make_db(rng, n_a=200)
    p = sql_to_forelem(CORE_QUERIES[2], SCHEMAS)
    plan = PartitionedPlan(p, db, PartitionedChoices(n_partitions=5, schedule="fixed"))
    plan.run()
    per_part = {}
    for d in plan.dispatch_log:
        if d.op.startswith("join:"):
            per_part.setdefault(d.partition, 0)
            per_part[d.partition] += d.rows
    layout = plan._layout("A", "b_id")
    expected = {p_: int(layout.bounds[p_ + 1] - layout.bounds[p_]) for p_ in range(5)}
    assert per_part == {p_: n for p_, n in expected.items() if n > 0}


def test_describe_reports_distribution(rng):
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[0], SCHEMAS)
    plan = PartitionedPlan(
        p, db, PartitionedChoices(n_partitions=4, schedule="guided", partition_field=("A", "b_id"))
    )
    plan.run()
    d = plan.describe()
    assert "partition=A.b_id" in d and "K=4" in d and "schedule=guided" in d


def test_plain_codegen_choices_accepted(rng):
    # the registry hands every backend the same choices object; the
    # partitioned backend must wrap a bare CodegenChoices
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[0], SCHEMAS)
    plan = get_backend("partitioned").compile(p, db, CodegenChoices(agg_method="sort"))
    assert sorted(plan.run()["R"]) == ref_rows(p, db)


def test_unknown_schedule_rejected(rng):
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[0], SCHEMAS)
    with pytest.raises(ValueError):
        PartitionedPlan(p, db, PartitionedChoices(schedule="banana"))


def test_gss_alias_accepted_and_session_validates_early(rng):
    from repro.engine import EngineError

    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[0], SCHEMAS)
    # 'gss' (the loop_schedule spelling) canonicalizes to 'guided'
    plan = PartitionedPlan(p, db, PartitionedChoices(n_partitions=3, schedule="gss"))
    assert plan.choices.schedule == "guided"
    assert sorted(plan.run()["R"]) == ref_rows(p, db)
    # an unknown policy must fail at Session construction, not after planning
    with pytest.raises(EngineError):
        Session(backend="partitioned", schedule="banana")
    Session(backend="partitioned", schedule="gss")  # alias accepted


def test_tables_stay_host_resident(rng):
    # the bounded-memory premise: _global_cols must NOT upload full columns
    # to the device — only per-chunk slices are jnp arrays
    import jax.numpy as jnp

    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[2], SCHEMAS)
    plan = PartitionedPlan(p, db, PartitionedChoices(n_partitions=4))
    cols = plan._global_cols(None)
    for t, fs in cols.items():
        for f, arr in fs.items():
            assert not isinstance(arr, jnp.ndarray), f"{t}.{f} uploaded eagerly"


# ---------------------------------------------------------------------------
# pipeline + Session + planner integration
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", KS)
def test_optimize_backend_partitioned(rng, k):
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[4], SCHEMAS)
    res = optimize(p, db, OptimizeOptions(backend="partitioned", n_partitions=k, schedule="guided"))
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)


def test_session_partitioned_matches_jax(rng):
    cols = dict(
        url=(rng.zipf(1.3, 20_000) % 500).astype(np.int32),
        lat=rng.integers(0, 300, 20_000).astype(np.int32),
    )
    q = "SELECT url, SUM(lat) FROM logs GROUP BY url"
    sp = Session(n_parts=4, backend="partitioned", plan_cache=PlanCache()).register("logs", **cols)
    sj = Session(n_parts=4, backend="jax", plan_cache=PlanCache()).register("logs", **cols)
    assert sorted(sp.sql(q).rows) == sorted(sj.sql(q).rows)
    text = sp.explain(q)
    assert "K=" in text and "schedule=" in text and "partition=" in text


def test_cost_planner_partitioned_end_to_end(rng):
    db = make_db(rng)
    p = sql_to_forelem(CORE_QUERIES[4], SCHEMAS)
    res = optimize(
        p, db, OptimizeOptions(planner="cost", backend="partitioned", plan_cache=PlanCache())
    )
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)
    assert res.decision.chosen.n_partitions is not None
    assert res.decision.chosen.schedule in ("static", "fixed", "guided")
    assert "K=" in res.explain and "schedule=" in res.explain


def _synthetic_stats(n_rows, most_common_frac, n_distinct=4096):
    fs = FieldStats(name="k", n_rows=n_rows, n_distinct=n_distinct, is_numeric=True,
                    vmin=0.0, vmax=float(n_distinct - 1),
                    most_common_frac=most_common_frac, is_unique=False)
    fv = FieldStats(name="v", n_rows=n_rows, n_distinct=1000, is_numeric=True,
                    vmin=0.0, vmax=999.0, most_common_frac=1.0 / 1000)
    return DbStats({"t": TableStats("t", n_rows, {"k": fs, "v": fv})}, epoch="synthetic")


def test_planner_partitions_when_working_set_exceeds_memory():
    p = sql_to_forelem("SELECT k, SUM(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    big = plan_query(p, _synthetic_stats(8_000_000, 1 / 4096), n_parts=8, executor="partitioned")
    small = plan_query(p, _synthetic_stats(5_000, 1 / 4096), n_parts=8, executor="partitioned")
    assert big.chosen.n_partitions > 1          # spill penalty beats launch overhead
    assert small.chosen.n_partitions == 1       # launch overhead wins on small data
    assert small.chosen.schedule == "static"


def test_planner_prefers_dynamic_schedule_on_skew():
    p = sql_to_forelem("SELECT k, SUM(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    uniform = plan_query(p, _synthetic_stats(8_000_000, 1 / 4096), n_parts=8, executor="partitioned")
    skewed = plan_query(p, _synthetic_stats(8_000_000, 0.45), n_parts=8, executor="partitioned")
    assert uniform.chosen.schedule == "static"  # fewest dispatches, no imbalance
    assert skewed.chosen.schedule in ("fixed", "guided")


def test_planner_respects_pinned_k_and_schedule():
    p = sql_to_forelem("SELECT k, SUM(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    d = plan_query(p, _synthetic_stats(50_000, 1 / 4096), n_parts=8,
                   executor="partitioned", n_partitions=6, schedule="guided")
    assert d.chosen.n_partitions == 6 and d.chosen.schedule == "guided"
    assert all(c.n_partitions == 6 and c.schedule == "guided" for c in d.candidates)


# ---------------------------------------------------------------------------
# satellite bugfix: shard_map MAX/MIN no longer raises UnsupportedProgram
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("agg", ["MAX", "MIN", "SUM"])
def test_shard_map_minmax_fixed(rng, agg):
    k = rng.integers(0, 6, 301).astype(np.int32)
    v = rng.integers(-80, -20, 301).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    p = sql_to_forelem(f"SELECT k, {agg}(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    res = optimize(p, db, OptimizeOptions(n_parts=4, parallel_exec="shard_map", mesh=mesh))
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)


@pytest.mark.parametrize("agg", ["MAX", "SUM", "COUNT"])
def test_shard_map_segreduce_kernel(rng, monkeypatch, agg):
    """agg_method='kernel' under shard_map: the Pallas kernel (interpret
    mode here) runs inside the SPMD body and its partials combine."""
    monkeypatch.setenv("REPRO_PALLAS", "1")
    k = rng.integers(0, 70, 3001).astype(np.int32)
    v = rng.integers(-80, 80, 3001).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    p = sql_to_forelem(f"SELECT k, {agg}(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    res = optimize(
        p, db,
        OptimizeOptions(n_parts=4, agg_method="kernel", parallel_exec="shard_map", mesh=mesh),
    )
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)
