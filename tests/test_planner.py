# Cost-based planner (repro.planner): statistics, cardinality-estimate
# accuracy vs. actual row counts, cost-model ranking sanity (the chosen plan
# must not be slower than the worst enumerated plan), join-order
# interchange, plan-cache hit/invalidation on stats-epoch change, EXPLAIN,
# and SQL ORDER BY / LIMIT end to end.
import time

import numpy as np
import pytest

import jax

from repro.core import OptimizeOptions, optimize
from repro.core.lower import CodegenChoices, Plan, ReferenceInterpreter
from repro.core.transforms import join_orders
from repro.data.multiset import Database, Multiset
from repro.frontends.sql import SQLError, sql_to_forelem
from repro.planner import (
    CardinalityEstimator,
    PlanCache,
    collect_stats,
    enumerate_candidates,
    plan_query,
    program_fingerprint,
    render_explain,
)


@pytest.fixture
def db(rng):
    k = rng.integers(0, 50, 4000).astype(np.int32)
    v = rng.integers(0, 100, 4000).astype(np.int32)
    return Database().add(Multiset.from_columns("t", k=k, v=v)), k, v


# ---------------------------------------------------------------------------
# statistics
# ---------------------------------------------------------------------------


def test_stats_basic(db):
    d, k, v = db
    stats = collect_stats(d)
    ts = stats.table("t")
    assert ts.n_rows == 4000
    fk = ts.field_stats("k")
    assert fk.n_distinct == len(np.unique(k))
    assert fk.vmin == float(k.min()) and fk.vmax == float(k.max())
    assert sum(fk.hist_counts) == pytest.approx(4000, rel=0.01)
    assert 0 < fk.most_common_frac < 1


def test_stats_epoch_deterministic_and_sensitive(rng):
    a = rng.integers(0, 9, 500).astype(np.int32)
    db1 = Database().add(Multiset.from_columns("t", a=a))
    db2 = Database().add(Multiset.from_columns("t", a=a.copy()))
    assert db1.stats_epoch() == db2.stats_epoch()  # content-determined
    db3 = Database().add(Multiset.from_columns("t", a=np.concatenate([a, a[:3]])))
    assert db3.stats_epoch() != db1.stats_epoch()  # rows added → new epoch


# ---------------------------------------------------------------------------
# cardinality estimation vs. actual counts
# ---------------------------------------------------------------------------


def test_cardinality_range_filter_accuracy(db):
    d, k, v = db
    stats = collect_stats(d)
    p = sql_to_forelem("SELECT k FROM t WHERE v < 37", {"t": ["k", "v"]})
    est = CardinalityEstimator(stats)
    filtered = p.body[0].indexset
    got = est.indexset_rows(filtered, {})
    actual = int((v < 37).sum())
    assert got == pytest.approx(actual, rel=0.3)


def test_cardinality_equality_and_groupby(db):
    d, k, v = db
    stats = collect_stats(d)
    est = CardinalityEstimator(stats)
    p = sql_to_forelem("SELECT v FROM t WHERE k = 7", {"t": ["k", "v"]})
    got = est.indexset_rows(p.body[0].indexset, {})
    actual = int((k == 7).sum())
    # uniform keys: 1/n_distinct is a good estimate
    assert got == pytest.approx(actual, rel=0.5)
    assert est.groupby_output("t", "k") == len(np.unique(k))


def test_loop_estimates_propagate_through_nesting(db):
    d, k, v = db
    stats = collect_stats(d)
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k", "v"]})
    ests = CardinalityEstimator(stats).loop_estimates(p)
    assert len(ests) == 2  # scan loop + distinct loop
    assert ests[0].total == pytest.approx(4000)
    assert ests[1].total == pytest.approx(len(np.unique(k)))


# ---------------------------------------------------------------------------
# join-order enumeration (interchange hook)
# ---------------------------------------------------------------------------


def test_join_orders_preserve_semantics(rng):
    # duplicated fk side: IR-level interchange must preserve semantics
    # (checked on the reference interpreter, which handles duplicates)
    A = Multiset.from_columns("A", b_id=rng.integers(0, 30, 120).astype(np.int32),
                              f=rng.integers(0, 9, 120).astype(np.int32))
    B = Multiset.from_columns("B", id=np.arange(30).astype(np.int32),
                              g=rng.integers(0, 9, 30).astype(np.int32))
    d = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id",
                       {"A": ["b_id", "f"], "B": ["id", "g"]})
    variants = join_orders(p)
    assert len(variants) == 1
    ref = sorted(ReferenceInterpreter(d).run(p)["R"])
    for variant in variants:
        assert sorted(ReferenceInterpreter(d).run(variant)["R"]) == ref


def test_join_orders_jax_lowering_1to1(rng):
    # both keys unique (1:1 join): every orientation lowers and agrees
    A = Multiset.from_columns("A", b_id=rng.permutation(40).astype(np.int32),
                              f=rng.integers(0, 9, 40).astype(np.int32))
    B = Multiset.from_columns("B", id=np.arange(40).astype(np.int32),
                              g=rng.integers(0, 9, 40).astype(np.int32))
    d = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id",
                       {"A": ["b_id", "f"], "B": ["id", "g"]})
    ref = sorted(ReferenceInterpreter(d).run(p)["R"])
    assert sorted(Plan(p, d).run()["R"]) == ref
    for variant in join_orders(p):
        assert sorted(Plan(variant, d).run()["R"]) == ref


def test_join_duplicate_build_keys_expand(rng):
    # both sides duplicated (many-to-many): the expansion lowering must
    # produce every match pair, exactly like the reference interpreter
    A = Multiset.from_columns("A", b_id=rng.integers(0, 5, 50).astype(np.int32))
    B = Multiset.from_columns("B", id=rng.integers(0, 5, 50).astype(np.int32))
    d = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.b_id, b.id FROM A a, B b WHERE a.b_id = b.id",
                       {"A": ["b_id"], "B": ["id"]})
    got = sorted(Plan(p, d).run()["R"])
    assert got == sorted(ReferenceInterpreter(d).run(p)["R"])
    # forcing the unique-lookup lowering onto duplicate keys must refuse
    from repro.core.lower import UnsupportedProgram

    with pytest.raises(UnsupportedProgram):
        Plan(p, d, CodegenChoices(join_method="lookup"))


def test_planner_enumerates_join_orders(rng):
    # 1:1 join: both orientations are key-unique, so both are enumerated
    A = Multiset.from_columns("A", b_id=rng.permutation(200).astype(np.int32))
    B = Multiset.from_columns("B", id=np.arange(200).astype(np.int32))
    d = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.b_id, b.id FROM A a, B b WHERE a.b_id = b.id",
                       {"A": ["b_id"], "B": ["id"]})
    cands = enumerate_candidates(p, collect_stats(d))
    orders = {c.order for c in cands}
    assert "as-written" in orders and any(o.startswith("interchanged") for o in orders)


def test_planner_join_method_per_orientation(rng):
    # fk side duplicated: the as-written orientation (unique build) may use
    # the cheap lookup; the interchanged orientation (duplicate build keys)
    # must only be offered with the expansion lowering — and every
    # enumerated candidate must execute to the reference answer
    A = Multiset.from_columns("A", b_id=rng.integers(0, 30, 500).astype(np.int32),
                              f=rng.integers(0, 9, 500).astype(np.int32))
    B = Multiset.from_columns("B", id=np.arange(30).astype(np.int32),
                              g=rng.integers(0, 9, 30).astype(np.int32))
    d = Database().add(A).add(B)
    p = sql_to_forelem("SELECT a.f, b.g FROM A a, B b WHERE a.b_id = b.id",
                       {"A": ["b_id", "f"], "B": ["id", "g"]})
    decision = plan_query(p, collect_stats(d))
    pairs = {(c.order, c.join_method) for c in decision.candidates}
    assert ("as-written", "lookup") in pairs
    assert ("interchanged[0]", "expand") in pairs
    assert ("interchanged[0]", "lookup") not in pairs
    # the unique-build lookup orientation is the cheap one
    assert decision.chosen.join_method == "lookup"
    ref = sorted(ReferenceInterpreter(d).run(p)["R"])
    for c in decision.candidates:
        got = sorted(Plan(c.program, d, CodegenChoices(join_method=c.join_method)).run()["R"])
        assert got == ref


# ---------------------------------------------------------------------------
# cost-model ranking sanity
# ---------------------------------------------------------------------------


def _timed(plan: Plan, repeats: int = 3) -> float:
    cols = plan.input_columns()
    jax.block_until_ready(plan.fn(cols))  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(plan.fn(cols))
        best = min(best, time.perf_counter() - t0)
    return best


def test_chosen_plan_not_slower_than_worst(rng):
    # many keys: the one-hot matmul (rows × keys work) is catastrophically
    # worse than dense scatter-add; the model must reflect that ordering
    k = rng.integers(0, 2000, 50_000).astype(np.int32)
    d = Database().add(Multiset.from_columns("t", k=k))
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k"]})
    decision = plan_query(p, collect_stats(d))
    chosen, worst = decision.candidates[0], decision.candidates[-1]
    assert chosen.cost <= worst.cost
    assert chosen.agg_method != "onehot"
    t_chosen = _timed(Plan(chosen.program, d, CodegenChoices(agg_method=chosen.agg_method)))
    t_worst = _timed(Plan(worst.program, d, CodegenChoices(agg_method=worst.agg_method)))
    assert t_chosen <= t_worst * 1.2


@pytest.mark.parametrize("key_space,want_kernel", [(3000, True), (1 << 20, False)])
def test_compiled_kernel_priced_per_key_tile(monkeypatch, rng, key_space, want_kernel):
    """The compiled segreduce kernel streams the rows once per 64-key tile:
    priced as it runs on a TPU, it wins a 3000-key COUNT GROUP BY and loses
    a 2^20-key one, whatever the row count."""
    from repro.planner import cost

    monkeypatch.setattr(cost, "pallas_mode", lambda: "compiled")
    k = rng.integers(0, key_space, 200_000).astype(np.int32)
    k[0] = key_space - 1  # the key space is max + 1
    d = Database().add(Multiset.from_columns("t", k=k))
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k"]})
    decision = plan_query(p, collect_stats(d))
    assert (decision.chosen.agg_method == "kernel") == want_kernel
    model = cost.CostModel(collect_stats(d))
    one_tile = model.agg_cost(1e6, 64, "kernel", "+")
    two_tiles = model.agg_cost(1e6, 65, "kernel", "+")
    assert two_tiles - one_tile > 0.9 * (one_tile - model.coeffs.c_kernel_fixed)


def test_planner_matches_fixed_defaults_results(db):
    d, k, v = db
    p = sql_to_forelem("SELECT k, COUNT(k), SUM(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    fixed = optimize(p, d, OptimizeOptions(n_parts=4, planner="none"))
    planned = optimize(p, d, OptimizeOptions(n_parts=4, planner="cost", plan_cache=PlanCache()))
    assert sorted(planned.plan.run()["R"]) == sorted(fixed.plan.run()["R"])
    assert planned.decision is not None
    assert planned.decision.chosen.agg_method in ("dense", "sort", "onehot", "kernel")
    assert planned.explain and "EXPLAIN" in planned.explain


def test_unknown_planner_rejected(db):
    d, _, _ = db
    p = sql_to_forelem("SELECT k FROM t", {"t": ["k", "v"]})
    with pytest.raises(ValueError):
        optimize(p, d, OptimizeOptions(planner="bogus"))


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------


def test_plan_cache_hit_and_epoch_invalidation(rng):
    k = rng.integers(0, 12, 1000).astype(np.int32)
    d = Database().add(Multiset.from_columns("t", k=k))
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k"]})
    cache = PlanCache()
    opts = OptimizeOptions(planner="cost", plan_cache=cache)
    r1 = optimize(p, d, opts)
    assert not r1.cache_hit and cache.stats()["misses"] == 1
    r2 = optimize(p, d, opts)
    assert r2.cache_hit and cache.stats()["hits"] == 1
    assert sorted(r2.plan.run()["R"]) == sorted(r1.plan.run()["R"])
    # data change → stats epoch change → miss (and correct new results)
    d2 = Database().add(Multiset.from_columns("t", k=np.concatenate([k, k])))
    r3 = optimize(p, d2, opts)
    assert not r3.cache_hit
    assert dict(r3.plan.run()["R"]) == {kk: 2 * c for kk, c in r1.plan.run()["R"]}


def test_plan_cache_invalidates_on_midcolumn_edit():
    # head/tail-only fingerprints would collide here and serve stale results
    s1 = np.full(1000, 200, np.int32)
    s2 = s1.copy()
    s2[100:900] = 500
    db1 = Database().add(Multiset.from_columns("t", status=s1))
    db2 = Database().add(Multiset.from_columns("t", status=s2))
    assert db1.stats_epoch() != db2.stats_epoch()
    p = sql_to_forelem("SELECT status, COUNT(status) FROM t GROUP BY status", {"t": ["status"]})
    cache = PlanCache()
    optimize(p, db1, OptimizeOptions(planner="cost", plan_cache=cache))
    r2 = optimize(p, db2, OptimizeOptions(planner="cost", plan_cache=cache))
    assert not r2.cache_hit
    assert sorted(r2.plan.run()["R"]) == [(200, 200), (500, 800)]


def test_plan_cache_keyed_on_planning_inputs(rng):
    # a plan compiled for n_parts=1 must not satisfy an n_parts=8 request
    d = Database().add(Multiset.from_columns("t", k=rng.integers(0, 9, 500).astype(np.int32)))
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k"]})
    cache = PlanCache()
    optimize(p, d, OptimizeOptions(planner="cost", plan_cache=cache, n_parts=1))
    r = optimize(p, d, OptimizeOptions(planner="cost", plan_cache=cache, n_parts=8))
    assert not r.cache_hit


def test_dict_column_stats_exact_under_sampling():
    # 300k rows exceeds the stats sampling cap; the dictionary still gives
    # exact distinct counts and key-uniqueness
    from repro.data.multiset import dict_encode

    vals = np.array([f"u{i % 7}" for i in range(300_000)], dtype=object)
    d = Database().add(Multiset("t", {"k": dict_encode(vals)}))
    fs = collect_stats(d).field("t", "k")
    assert fs.n_distinct == 7
    assert fs.is_unique is False


def test_plan_cache_distinguishes_programs(db):
    d, _, _ = db
    p1 = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k", "v"]})
    p2 = sql_to_forelem("SELECT k, SUM(v) FROM t GROUP BY k", {"t": ["k", "v"]})
    assert program_fingerprint(p1) != program_fingerprint(p2)
    p3 = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k", "v"]})
    assert program_fingerprint(p1) == program_fingerprint(p3)


def test_plan_cache_lru_eviction():
    cache = PlanCache(capacity=2)
    from repro.planner.cache import CacheEntry

    for i in range(3):
        cache.put(f"fp{i}", "e", CacheEntry(None, None, "", None, "e"))
    assert len(cache) == 2
    assert cache.get("fp0", "e") is None  # evicted
    assert cache.get("fp2", "e") is not None


# ---------------------------------------------------------------------------
# EXPLAIN
# ---------------------------------------------------------------------------


def test_explain_shows_estimates_and_choices(db):
    d, k, v = db
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k", "v"]})
    decision = plan_query(p, collect_stats(d))
    text = render_explain(decision, name="q")
    assert "EXPLAIN q" in text
    assert "rows≈" in text and "est_cost≈" in text
    assert "agg_method=" in text and "rejected alternatives" in text


# ---------------------------------------------------------------------------
# ORDER BY / LIMIT (SQL frontend + lowering)
# ---------------------------------------------------------------------------


def test_order_by_limit_topk(db):
    d, k, v = db
    p = sql_to_forelem(
        "SELECT k, COUNT(k) AS c FROM t GROUP BY k ORDER BY c DESC LIMIT 3", {"t": ["k", "v"]}
    )
    got = Plan(p, d).run()["R"]
    vals, counts = np.unique(k, return_counts=True)
    want = sorted(zip(vals.tolist(), counts.tolist()), key=lambda r: -r[1])[:3]
    assert [c for _, c in got] == [c for _, c in want]
    # count column agrees with the reference (tie order among equal counts
    # is unspecified, so compare the ordered count column only)
    ref = ReferenceInterpreter(d).run(p)["R"]
    assert [c for _, c in ref] == [c for _, c in got]


def test_order_by_asc_on_projection(db):
    d, k, v = db
    p = sql_to_forelem("SELECT v FROM t WHERE k = 3 ORDER BY v ASC LIMIT 10", {"t": ["k", "v"]})
    got = [r[0] for r in Plan(p, d).run()["R"]]
    want = sorted(v[k == 3].tolist())[:10]
    assert got == want


def test_order_by_errors():
    with pytest.raises(SQLError):
        sql_to_forelem("SELECT k FROM t ORDER BY nope", {"t": ["k"]})
    with pytest.raises(SQLError):
        sql_to_forelem("SELECT SUM(k) FROM t LIMIT 2", {"t": ["k"]})
