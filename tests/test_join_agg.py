# Differential reference-vs-JAX tests for the general equi-join engine
# (duplicate build keys via sort + searchsorted(left/right) + gather
# expansion; a dense integer build key through a position table), GROUP BY
# over a two-table join, and the filtered MIN/MAX
# aggregation paths across every agg_method.  The ReferenceInterpreter is
# the oracle throughout.
import numpy as np
import pytest

from ab_tables import SCHEMAS, make_db, ref_rows
from repro.core import OptimizeOptions, optimize
from repro.core.lower import (
    CodegenChoices,
    JaxLowering,
    Plan,
    ReferenceInterpreter,
    UnsupportedProgram,
    extract_spec,
)
from repro.data.multiset import Database, Multiset
from repro.frontends.sql import SQLError, sql_to_forelem
from repro.planner import PlanCache, collect_stats, plan_query

AGG_METHODS = ("dense", "onehot", "sort", "kernel")


# ---------------------------------------------------------------------------
# filtered MIN/MAX across all four agg_methods (satellite: identity masking)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", AGG_METHODS)
@pytest.mark.parametrize("agg", ["MIN", "MAX", "SUM"])
def test_filtered_minmax_all_agg_methods(rng, method, agg):
    # all-negative values in segment 0 expose the old `masked → (key=0,
    # value=0)` corruption: a masked 0 would win MAX over any negative max
    k = rng.integers(0, 8, 400).astype(np.int32)
    v = rng.integers(-100, -1, 400).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    p = sql_to_forelem(f"SELECT k, {agg}(v) FROM t WHERE v < -10 GROUP BY k", {"t": ["k", "v"]})
    got = sorted(Plan(p, db, CodegenChoices(agg_method=method)).run()["R"])
    assert got == ref_rows(p, db)


@pytest.mark.parametrize("method", AGG_METHODS)
def test_filtered_minmax_emptied_group_densifies(rng, method):
    # group 3 is emptied by the filter: it must vanish from the result (no
    # -inf / int-min sentinel rows escaping the presence mask)
    k = np.array([0, 0, 1, 1, 2, 3, 3], np.int32)
    v = np.array([5, -7, 9, 2, -4, 100, 100], np.int32)
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    p = sql_to_forelem("SELECT k, MIN(v), MAX(v) FROM t WHERE v < 50 GROUP BY k", {"t": ["k", "v"]})
    got = sorted(Plan(p, db, CodegenChoices(agg_method=method)).run()["R"])
    assert got == ref_rows(p, db) == [(0, -7, 5), (1, 2, 9), (2, -4, -4)]


@pytest.mark.parametrize("agg", ["MIN", "MAX"])
def test_sort_method_minmax_not_sum(rng, agg):
    # agg_method='sort' used to funnel MIN/MAX into segment_sum
    k = rng.integers(0, 5, 100).astype(np.int32)
    v = rng.integers(1, 9, 100).astype(np.int32)  # sums differ from extrema
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    p = sql_to_forelem(f"SELECT k, {agg}(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    got = sorted(Plan(p, db, CodegenChoices(agg_method="sort")).run()["R"])
    assert got == ref_rows(p, db)


@pytest.mark.parametrize("method", AGG_METHODS)
def test_filtered_minmax_parallel_vmap_padding(rng, method):
    # n_parts that does not divide the row count exercises the pad path:
    # padded rows must contribute the op identity, not 0
    k = rng.integers(0, 6, 301).astype(np.int32)
    v = rng.integers(-80, -20, 301).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k, v=v))
    p = sql_to_forelem("SELECT k, MAX(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    res = optimize(p, db, OptimizeOptions(n_parts=4, agg_method=method, parallel_exec="vmap"))
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)


# ---------------------------------------------------------------------------
# duplicate-key joins
# ---------------------------------------------------------------------------


def test_join_fanout_gt_1_matches_reference(rng):
    db = make_db(rng, dup_build=True)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    ref = ref_rows(p, db)
    assert len(ref) > len(db["A"])  # genuine fan-out > 1
    assert sorted(Plan(p, db).run()["R"]) == ref


def test_join_unique_build_uses_lookup(rng):
    db = make_db(rng, dup_build=False)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    lowering = JaxLowering(p, db)
    assert lowering.join_multiplicity == [1]
    assert sorted(Plan(p, db).run()["R"]) == ref_rows(p, db)
    # forcing expansion on unique keys is also correct (M == 1 degenerate)
    got = sorted(Plan(p, db, CodegenChoices(join_method="expand")).run()["R"])
    assert got == ref_rows(p, db)


def test_join_empty_build_side(rng):
    A = Multiset.from_columns("A", b_id=rng.integers(0, 5, 20).astype(np.int32),
                              f=rng.integers(0, 4, 20).astype(np.int32),
                              w=rng.integers(-9, 9, 20).astype(np.int32))
    B = Multiset.from_columns("B", id=np.array([], np.int32), g=np.array([], np.int32),
                              v=np.array([], np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    assert Plan(p, db).run()["R"] == [] == ReferenceInterpreter(db).run(p)["R"]


def test_join_no_matching_probes(rng):
    # probe keys entirely outside the build key range: all probes miss
    A = Multiset.from_columns("A", b_id=(100 + rng.integers(0, 5, 20)).astype(np.int32),
                              f=rng.integers(0, 4, 20).astype(np.int32),
                              w=np.zeros(20, np.int32))
    B = Multiset.from_columns("B", id=rng.integers(0, 5, 10).astype(np.int32),
                              g=rng.integers(0, 4, 10).astype(np.int32),
                              v=np.zeros(10, np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    assert Plan(p, db).run()["R"] == [] == ReferenceInterpreter(db).run(p)["R"]


def test_join_probe_side_filter(rng):
    db = make_db(rng)
    p = sql_to_forelem(
        "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id AND a.w > 0", SCHEMAS
    )
    assert sorted(Plan(p, db).run()["R"]) == ref_rows(p, db)


def test_join_residual_orients_probe_side(rng):
    # the residual references the table on the RIGHT of the equality: the
    # nest must be re-oriented so the filtered table probes, not rejected
    db = make_db(rng)
    flipped = sql_to_forelem(
        "SELECT a.f, b.g FROM A a, B b WHERE b.id = a.b_id AND a.w > 0", SCHEMAS
    )
    straight = sql_to_forelem(
        "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id AND a.w > 0", SCHEMAS
    )
    assert sorted(Plan(flipped, db).run()["R"]) == ref_rows(flipped, db) == ref_rows(straight, db)


def test_join_residual_on_both_sides_rejected():
    with pytest.raises(SQLError):
        sql_to_forelem(
            "SELECT a.f FROM A a, B b WHERE a.b_id = b.id AND a.w + b.v > 0", SCHEMAS
        )


def test_lookup_forced_on_duplicates_refuses(rng):
    db = make_db(rng, dup_build=True)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    with pytest.raises(UnsupportedProgram):
        Plan(p, db, CodegenChoices(join_method="lookup"))


# ---------------------------------------------------------------------------
# direct-address probe of a dense unique integer build key
# ---------------------------------------------------------------------------

# case -> (build keys, probe keys, extra probe filter, expected probe)
PROBE_CASES = {
    "dense_unique": (lambda r: r.permutation(40), lambda r: r.integers(0, 40, 300), "", "direct"),
    "dense_duplicates": (lambda r: r.integers(0, 12, 40), lambda r: r.integers(0, 12, 300), "",
                         "search"),
    "offset_domain": (lambda r: 1000 + r.permutation(40), lambda r: r.integers(1000, 1040, 300),
                      "", "direct"),
    "negative_domain": (lambda r: r.permutation(40) - 25, lambda r: r.integers(-25, 15, 300), "",
                        "direct"),
    "probes_outside_domain": (lambda r: 10 + r.permutation(20), lambda r: r.integers(-5, 45, 300),
                              "", "direct"),
    "filter_empties_probe": (lambda r: r.permutation(40), lambda r: r.integers(0, 40, 300),
                             " AND a.w > 1000", "direct"),
    "sparse_domain": (lambda r: 8 * r.permutation(40), lambda r: 8 * r.integers(0, 40, 300), "",
                      "search"),
    # 118 slots fit 4x the 40 build rows but not the 2 x (40 + 10) the search holds
    "table_above_search_bytes": (lambda r: 3 * r.permutation(40),
                                 lambda r: 3 * r.integers(0, 40, 10), "", "search"),
}
PROBE_SQL = (
    "SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id{}",
    "SELECT b.g, COUNT(b.g), SUM(a.w), MIN(b.v) FROM A a, B b WHERE a.b_id = b.id{} GROUP BY b.g",
)


@pytest.mark.parametrize("join_method", ["auto", "expand"])
@pytest.mark.parametrize("sql", PROBE_SQL, ids=["join", "groupby"])
@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_direct_probe_matches_reference(case, sql, join_method):
    build, probe, where, expected = PROBE_CASES[case]
    r = np.random.default_rng(14)
    b_id, a_id = build(r).astype(np.int32), probe(r).astype(np.int32)
    A = Multiset.from_columns("A", b_id=a_id, f=r.integers(0, 6, len(a_id)).astype(np.int32),
                              w=r.integers(-50, 50, len(a_id)).astype(np.int32))
    B = Multiset.from_columns("B", id=b_id, g=r.integers(0, 5, len(b_id)).astype(np.int32),
                              v=r.integers(-30, 30, len(b_id)).astype(np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem(sql.format(where), SCHEMAS)
    plan = Plan(p, db, CodegenChoices(join_method=join_method))
    assert plan.lowering.join_probe == [expected]
    ref = ref_rows(p, db)
    assert sorted(plan.run()["R"]) == ref
    if where:
        assert ref == []


@pytest.fixture
def q3a_tables(monkeypatch):
    """BDB Q3A's tables at the benchmark's tiny CPU size, and its SQL."""
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(root))
    from bench import query, spec
    from bench.tests._tiny import tiny_config

    bench = spec.load_benchmark(root)
    cfg = tiny_config(bench, "bdb.join.batch")
    traffic = spec.traffic(spec.workload(bench, "bdb.join.batch")["traffic"])
    tables = spec.generator(cfg).generate(cfg, 2**31 + 14)
    return tables, query.to_sql(traffic["templates"][0]["query"])


@pytest.mark.parametrize("spread,probe,searches", [(1, "direct", False), (8, "search", True)])
def test_q3a_program_has_no_search_loop_on_a_dense_key(q3a_tables, spread, probe, searches):
    # the binary search lowers to a `while` of log2(build rows) gathers of
    # every probe row; a dense key (pageURL is a permutation) needs none,
    # a key spread 8x over its domain still searches
    from repro import Session

    tables, sql = q3a_tables
    s = Session(revalidate="signature")
    s.register("rankings", **dict(tables["rankings"], pageURL=tables["rankings"]["pageURL"] * spread))
    s.register("uservisits", **dict(tables["uservisits"],
                                    destURL=tables["uservisits"]["destURL"] * spread))
    plan = s.sql(sql).plan
    assert plan.lowering.join_probe == [probe]
    text = plan.fn.lower(plan.input_columns()).as_text()
    assert ("stablehlo.while" in text) == searches


def test_q3a_direct_probe_sums_bit_identical_to_search(q3a_tables, monkeypatch):
    # the position table matches the same pairs in the same probe-row order
    # as the width-1 expansion, so even the float sums agree to the bit
    import jax

    from repro import Session
    from repro.backends import jax_vec

    tables, sql = q3a_tables

    def raw_outputs():
        s = Session(revalidate="signature")
        for name, cols in tables.items():
            s.register(name, **cols)
        plan = s.sql(sql).plan
        return plan.lowering.join_probe, jax.tree.leaves(plan.fn(plan.input_columns()))

    probe, direct = raw_outputs()
    monkeypatch.setattr(jax_vec.JaxLowering, "_dense_key_range", lambda self, j, uniq: None)
    probe_searched, searched = raw_outputs()
    assert (probe, probe_searched) == (["direct"], ["search"])
    assert len(direct) == len(searched)
    for a, b in zip(direct, searched):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# GROUP BY over a two-table join
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sql", [
    "SELECT a.f, COUNT(a.f) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
    "SELECT a.f, SUM(b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
    "SELECT b.g, COUNT(b.g), SUM(a.w) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
    "SELECT b.g, MIN(a.w), MAX(b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
    "SELECT a.f, SUM(a.w + b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f",
])
def test_groupby_over_join_matches_reference(rng, sql):
    db = make_db(rng)
    p = sql_to_forelem(sql, SCHEMAS)
    assert sorted(Plan(p, db).run()["R"]) == ref_rows(p, db)


@pytest.mark.parametrize("method", AGG_METHODS)
def test_groupby_over_join_all_agg_methods(rng, method):
    db = make_db(rng)
    p = sql_to_forelem(
        "SELECT b.g, COUNT(b.g), MIN(a.w) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
        SCHEMAS,
    )
    got = sorted(Plan(p, db, CodegenChoices(agg_method=method)).run()["R"])
    assert got == ref_rows(p, db)


def test_groupby_over_join_avg(rng):
    db = make_db(rng)
    p = sql_to_forelem(
        "SELECT a.f, AVG(b.v) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f", SCHEMAS
    )
    got = sorted(Plan(p, db).run()["R"])
    ref = ref_rows(p, db)
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, rtol=1e-5)


def test_groupby_over_join_filtered_probe_empties_group(rng):
    # the probe filter can leave a group with zero joined rows — it must be
    # absent from both executors (presence-guarded distinct read)
    A = Multiset.from_columns("A", b_id=np.array([0, 0, 1, 1], np.int32),
                              f=np.array([0, 0, 1, 1], np.int32),
                              w=np.array([5, 6, -5, -6], np.int32))
    B = Multiset.from_columns("B", id=np.array([0, 1], np.int32),
                              g=np.array([0, 1], np.int32),
                              v=np.array([10, 20], np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem(
        "SELECT a.f, SUM(b.v) FROM A a, B b WHERE a.b_id = b.id AND a.w > 0 GROUP BY a.f",
        SCHEMAS,
    )
    got = sorted(Plan(p, db).run()["R"])
    assert got == ref_rows(p, db) == [(0, 20)]


def test_groupby_over_join_unmatched_group_absent(rng):
    # a dim row whose key never occurs in the fact table: GROUP BY b.g must
    # not emit a zero row for it
    A = Multiset.from_columns("A", b_id=np.array([0, 0], np.int32),
                              f=np.array([1, 2], np.int32), w=np.array([3, 4], np.int32))
    B = Multiset.from_columns("B", id=np.array([0, 7], np.int32),
                              g=np.array([0, 9], np.int32), v=np.array([1, 1], np.int32))
    db = Database().add(A).add(B)
    p = sql_to_forelem(
        "SELECT b.g, SUM(a.w) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g", SCHEMAS
    )
    got = sorted(Plan(p, db).run()["R"])
    assert got == ref_rows(p, db) == [(0, 7)]


def test_groupby_over_join_spec_shape(rng):
    p = sql_to_forelem(
        "SELECT a.f, COUNT(a.f) FROM A a, B b WHERE a.b_id = b.id GROUP BY a.f", SCHEMAS
    )
    spec = extract_spec(p)
    assert len(spec.joins) == 1 and spec.joins[0].result is None
    assert spec.joins[0].aggs and spec.joins[0].items == ()
    assert len(spec.distinct_reads) == 1
    assert spec.distinct_reads[0].filter_pred is not None


# ---------------------------------------------------------------------------
# planner + end-to-end Plan.run through optimize(planner='cost')
# ---------------------------------------------------------------------------


def test_cost_planner_executes_duplicate_key_join(rng):
    db = make_db(rng)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    res = optimize(p, db, OptimizeOptions(planner="cost", plan_cache=PlanCache()))
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)
    assert res.decision.chosen.join_method == "expand"
    assert "join_method=expand" in res.explain


def test_cost_planner_executes_groupby_over_join(rng):
    db = make_db(rng)
    p = sql_to_forelem(
        "SELECT b.g, COUNT(b.g), SUM(a.w) FROM A a, B b WHERE a.b_id = b.id GROUP BY b.g",
        SCHEMAS,
    )
    res = optimize(p, db, OptimizeOptions(planner="cost", plan_cache=PlanCache()))
    assert sorted(res.plan.run()["R"]) == ref_rows(p, db)


def test_cost_planner_picks_lookup_when_unique(rng):
    db = make_db(rng, dup_build=False)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)
    decision = plan_query(p, collect_stats(db))
    same_order = [c for c in decision.candidates if c.order == decision.chosen.order]
    by_method = {c.join_method: c.cost for c in same_order}
    assert by_method["lookup"] < by_method["expand"]
    assert decision.chosen.join_method == "lookup"


def test_expansion_cost_scales_with_multiplicity(rng):
    # heavier key duplication must make the expansion plan look costlier
    def db_with_mult(m):
        ids = np.repeat(np.arange(10), m).astype(np.int32)
        A = Multiset.from_columns("A", b_id=rng.integers(0, 10, 50).astype(np.int32),
                                  f=np.zeros(50, np.int32), w=np.zeros(50, np.int32))
        B = Multiset.from_columns("B", id=ids, g=np.zeros(len(ids), np.int32),
                                  v=np.zeros(len(ids), np.int32))
        return Database().add(A).add(B)

    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id", SCHEMAS)

    def expand_cost(db):
        decision = plan_query(p, collect_stats(db))
        return min(c.cost for c in decision.candidates
                   if c.order == "as-written" and c.join_method == "expand")

    assert expand_cost(db_with_mult(8)) > expand_cost(db_with_mult(2))


# ---------------------------------------------------------------------------
# ORDER BY fixes that ride along
# ---------------------------------------------------------------------------


def test_sampled_unique_key_multiplicity_not_stride_inflated():
    # 1M unique keys sampled at stride 4: a naive scale-up would report
    # max_multiplicity≈4 and overprice the expand join by the stride
    n = 1_000_000
    db = Database().add(Multiset.from_columns("t", k=np.arange(n, dtype=np.int64)))
    fs = collect_stats(db).field("t", "k")
    assert fs.is_unique is None  # sampled — uniqueness not provable
    assert fs.max_multiplicity == 1


def test_query_order_by_defaults_to_empty_tuple():
    from repro.frontends.sql import parse_sql

    q = parse_sql("SELECT k FROM t")
    assert q.order_by == ()


def test_order_by_unaliased_aggregate(rng):
    k = rng.integers(0, 7, 300).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k))
    p = sql_to_forelem(
        "SELECT k, COUNT(k) FROM t GROUP BY k ORDER BY COUNT(k) DESC LIMIT 3", {"t": ["k"]}
    )
    got = Plan(p, db).run()["R"]
    counts = sorted(np.unique(k, return_counts=True)[1].tolist(), reverse=True)[:3]
    assert [c for _, c in got] == counts


def test_order_by_unknown_aggregate_rejected():
    with pytest.raises(SQLError):
        sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k ORDER BY SUM(k)", {"t": ["k"]})
