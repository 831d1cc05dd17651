# The unified query engine: Session front door, SQL↔MapReduce equivalence
# through one planner pipeline and one plan cache, executor-backend
# registry, export_mr round-trips, and the stats-epoch invalidation
# semantics on table replacement.
import numpy as np
import pytest

from repro import MapReduceSpec, Session
from repro.backends import available_backends, get_backend
from repro.core import OptimizeOptions, optimize
from repro.core.ir import Const, FieldRef
from repro.core.transforms import canonicalize_array_names
from repro.data.multiset import Database, Multiset
from repro.engine import EngineError
from repro.frontends.export_mr import NotMapReduceShape, forelem_to_mapreduce
from repro.frontends.mapreduce import mapreduce_to_forelem, run_python_mapreduce
from repro.frontends.sql import sql_to_forelem
from repro.planner import PlanCache, program_fingerprint


@pytest.fixture
def web_session(rng):
    urls = rng.integers(0, 17, 800).astype(np.int32)
    lat = rng.gamma(2.0, 30.0, 800).astype(np.float32)
    s = Session(n_parts=4)
    s.register("access", url=urls, latency=lat)
    return s, urls, lat


# ---------------------------------------------------------------------------
# SQL ↔ MapReduce equivalence through the Session
# ---------------------------------------------------------------------------


def test_sql_mapreduce_same_results_and_shared_plan_cache_entry(web_session):
    s, urls, _ = web_session
    r_sql = s.sql("SELECT url, COUNT(url) FROM access GROUP BY url")
    assert r_sql.cache_hit is False
    r_mr = s.mapreduce(MapReduceSpec.count("access", "url"))
    # identical logical query → identical results AND a plan-cache hit
    assert sorted(r_mr.rows) == sorted(r_sql.rows)
    assert r_mr.cache_hit is True
    assert len(s.plan_cache) == 1  # one shared entry, not two
    vals, counts = np.unique(urls, return_counts=True)
    assert sorted(r_sql.rows) == [(int(v), int(c)) for v, c in zip(vals, counts)]


def test_sql_mapreduce_sum_by_key_equivalence(web_session):
    s, urls, lat = web_session
    r_mr = s.mapreduce(MapReduceSpec.aggregate("access", "url", "latency", "+"))
    r_sql = s.sql("SELECT url, SUM(latency) FROM access GROUP BY url")
    assert r_sql.cache_hit is True  # MR came first; SQL reuses its plan
    a = {k: v for k, v in r_mr.rows}
    b = {k: v for k, v in r_sql.rows}
    assert set(a) == set(b)
    for k in a:
        assert a[k] == pytest.approx(b[k], rel=1e-5)


def test_canonicalized_fingerprints_match_across_frontends():
    sql_p = sql_to_forelem(
        "SELECT url, COUNT(url) FROM access GROUP BY url", {"access": ["url"]}
    )
    mr_p = mapreduce_to_forelem(MapReduceSpec("access", "url", Const(1)), ["url"])
    assert program_fingerprint(canonicalize_array_names(sql_p)) == program_fingerprint(
        canonicalize_array_names(mr_p)
    )
    # without canonicalization the internal array names differ
    assert program_fingerprint(sql_p) != program_fingerprint(mr_p)


def test_mapreduce_gets_planner_explain(web_session):
    s, _, _ = web_session
    text = s.explain(MapReduceSpec.count("access", "url"))
    assert "EXPLAIN" in text and "chosen:" in text
    assert "agg_method=" in text


def test_warm_dispatch_and_repeat_submission(web_session):
    s, _, _ = web_session
    q = "SELECT url, COUNT(url) FROM access GROUP BY url"
    r1 = s.sql(q)
    r2 = s.sql(q)
    assert r1.dispatch_hit is False
    assert r2.dispatch_hit is True and r2.cache_hit is True
    assert sorted(r1.rows) == sorted(r2.rows)


def test_mapreduce_params_and_reference_backend(rng):
    k = rng.integers(0, 5, 200).astype(np.int32)
    v = rng.integers(0, 50, 200).astype(np.int32)
    out = {}
    for backend in ("jax", "reference"):
        s = Session(backend=backend)
        s.register("t", k=k, v=v)
        out[backend] = sorted(s.mapreduce(MapReduceSpec.aggregate("t", "k", "v", "max")).rows)
    assert out["jax"] == out["reference"]


# ---------------------------------------------------------------------------
# Stats epoch / plan-cache invalidation on table replacement
# ---------------------------------------------------------------------------


def test_replacing_table_invalidates_old_epoch_plans():
    s = Session(n_parts=2)
    s.register("t", k=np.array([0, 1, 0, 1, 2], dtype=np.int32),
               v=np.arange(5, dtype=np.int32))
    r1 = s.sql("SELECT k, SUM(v) FROM t GROUP BY k")
    assert sorted(r1.rows) == [(0, 2), (1, 4), (2, 4)]
    assert len(s.plan_cache) == 1
    # replace with different content: the old compiled plan baked in a
    # key space of 3 — serving it against the new data would be wrong
    s.register("t", k=np.array([5, 5, 6], dtype=np.int32),
               v=np.array([10, 20, 30], dtype=np.int32))
    assert len(s.plan_cache) == 0  # invalidate_epoch dropped the stale entry
    r2 = s.sql("SELECT k, SUM(v) FROM t GROUP BY k")
    assert r2.cache_hit is False
    assert sorted(r2.rows) == [(5, 30), (6, 30)]


def test_identical_content_replacement_still_bumps_epoch():
    k = np.array([1, 1, 2], dtype=np.int32)
    s = Session()
    s.register("t", k=k)
    s.sql("SELECT k, COUNT(k) FROM t GROUP BY k")
    e0 = s.stats_epoch()
    s.register("t", k=k.copy())  # same bytes — content fingerprint agrees
    assert s.stats_epoch() != e0  # the explicit bump still forces a new epoch
    r = s.sql("SELECT k, COUNT(k) FROM t GROUP BY k")
    assert r.cache_hit is False and sorted(r.rows) == [(1, 2), (2, 1)]


def test_out_of_band_db_mutation_is_not_served_stale_plans():
    # Session.db is public and mutable; a table swapped in behind the
    # Session's back must still invalidate the warm-dispatch memo (the
    # epoch is revalidated per dispatch, not trusted from the last refresh)
    s = Session()
    s.register("t", k=np.array([0, 1, 0, 1, 2], dtype=np.int32),
               v=np.arange(5, dtype=np.int32))
    q = "SELECT k, SUM(v) FROM t GROUP BY k"
    assert sorted(s.sql(q).rows) == [(0, 2), (1, 4), (2, 4)]
    s.db.add(Multiset.from_columns("t", k=np.array([9, 9], dtype=np.int32),
                                   v=np.array([1, 2], dtype=np.int32)))
    r = s.sql(q)
    assert r.dispatch_hit is False
    assert sorted(r.rows) == [(9, 3)]


def test_in_place_column_edit_is_revalidated():
    # the default revalidate='content' catches buffer mutation that leaves
    # the table object (and its id/length) unchanged
    s = Session()
    s.register("t", k=np.array([0, 1, 0, 1, 2], dtype=np.int32),
               v=np.array([1, 1, 1, 1, 1], dtype=np.int32))
    q = "SELECT k, SUM(v) FROM t GROUP BY k"
    assert sorted(s.sql(q).rows) == [(0, 2), (1, 2), (2, 1)]
    s.db["t"].columns["k"].values[:] = np.array([7, 7, 7, 8, 8], dtype=np.int32)
    r = s.sql(q)
    assert r.dispatch_hit is False
    assert sorted(r.rows) == [(7, 3), (8, 2)]


def test_signature_revalidation_mode_catches_table_swap():
    s = Session(revalidate="signature")
    s.register("t", k=np.array([0, 1], dtype=np.int32), v=np.array([1, 2], dtype=np.int32))
    q = "SELECT k, SUM(v) FROM t GROUP BY k"
    assert sorted(s.sql(q).rows) == [(0, 1), (1, 2)]
    s.db.add(Multiset.from_columns("t", k=np.array([3], dtype=np.int32),
                                   v=np.array([9], dtype=np.int32)))
    assert sorted(s.sql(q).rows) == [(3, 9)]
    with pytest.raises(EngineError):
        Session(revalidate="bogus")


def test_history_is_metadata_only(web_session):
    s, _, _ = web_session
    s.sql("SELECT url, COUNT(url) FROM access GROUP BY url")
    entry = s.history[-1]
    assert entry.source == "sql" and entry.elapsed_s > 0
    assert not hasattr(entry, "results") and not hasattr(entry, "plan")


def test_schema_changing_replace_reparses_programs():
    # the frontend parse memo must not survive a schema change: the old
    # program binds columns that no longer exist
    s = Session()
    s.register("t", k=np.array([0, 1], dtype=np.int32), v=np.array([1, 2], dtype=np.int32))
    q_old = "SELECT k, SUM(v) FROM t GROUP BY k"
    assert sorted(s.sql(q_old).rows) == [(0, 1), (1, 2)]
    s.register("t", k=np.array([0, 1], dtype=np.int32), w=np.array([5, 6], dtype=np.int32))
    with pytest.raises(Exception):
        s.sql(q_old)  # column v is gone — must error, not run a stale plan
    assert sorted(s.sql("SELECT k, SUM(w) FROM t GROUP BY k").rows) == [(0, 5), (1, 6)]


def test_drop_table_invalidates(web_session):
    s, _, _ = web_session
    s.sql("SELECT url, COUNT(url) FROM access GROUP BY url")
    assert len(s.plan_cache) == 1
    s.drop("access")
    assert len(s.plan_cache) == 0
    assert "access" not in s.db
    with pytest.raises(EngineError):
        s.drop("access")


def test_register_rejects_bad_arguments():
    s = Session()
    with pytest.raises(EngineError):
        s.register("t")  # no columns
    with pytest.raises(EngineError):
        s.register(Multiset.from_columns("t", k=np.arange(3)), k=np.arange(3))
    with pytest.raises(EngineError):
        s.mapreduce(MapReduceSpec.count("missing", "k"))


# ---------------------------------------------------------------------------
# Backend registry and the core/lower.py compat shim
# ---------------------------------------------------------------------------


def test_backend_registry_names():
    assert {"jax", "reference"} <= set(available_backends())
    with pytest.raises(KeyError):
        get_backend("no-such-backend")


def test_backends_are_keyed_separately_in_plan_cache(rng):
    k = rng.integers(0, 4, 100).astype(np.int32)
    db = Database().add(Multiset.from_columns("t", k=k))
    cache = PlanCache()
    p = sql_to_forelem("SELECT k, COUNT(k) FROM t GROUP BY k", {"t": ["k"]})
    r_jax = optimize(p, db, OptimizeOptions(planner="cost", plan_cache=cache, backend="jax"))
    r_ref = optimize(p, db, OptimizeOptions(planner="cost", plan_cache=cache, backend="reference"))
    assert r_jax.cache_hit is False and r_ref.cache_hit is False
    assert len(cache) == 2  # one compiled plan per backend
    assert sorted(r_jax.plan.run()["R"]) == sorted(r_ref.plan.run()["R"])


def test_lower_shim_reexports():
    from repro.core import lower

    from repro.backends import jax_vec, reference, codegen

    assert lower.Plan is jax_vec.Plan
    assert lower.CodegenChoices is jax_vec.CodegenChoices
    assert lower.ReferenceInterpreter is reference.ReferenceInterpreter
    assert lower.extract_spec is codegen.extract_spec
    assert lower.UnsupportedProgram is codegen.UnsupportedProgram


# ---------------------------------------------------------------------------
# export_mr round trips (forelem → MapReduce → Hadoop-style execution)
# ---------------------------------------------------------------------------


def _run_exported(mr, ms):
    fields = ms.field_names()
    cols = {f: np.asarray(ms.field(f)) for f in fields}
    rows = (
        (i, {f: cols[f][i].item() for f in fields})
        for i in range(len(ms))
    )
    return sorted(run_python_mapreduce(mr.map_fn, mr.reduce_fn, rows, 4))


def test_export_mr_roundtrip_count(web_session):
    s, _, _ = web_session
    r = s.sql("SELECT url, COUNT(url) FROM access GROUP BY url")
    mr = forelem_to_mapreduce(
        sql_to_forelem("SELECT url, COUNT(url) FROM access GROUP BY url", s.schemas())
    )
    got = _run_exported(mr, s.db["access"])
    assert got == sorted(r.rows)
    assert "emitIntermediate" in mr.pseudocode


def test_export_mr_roundtrip_sum(rng):
    k = rng.integers(0, 6, 150).astype(np.int32)
    v = rng.integers(0, 30, 150).astype(np.int32)
    s = Session()
    s.register("t", k=k, v=v)
    spec = MapReduceSpec.aggregate("t", "k", "v", "+")
    r = s.mapreduce(spec)
    # engine → IR → exported MR program → Hadoop-style executor
    prog = mapreduce_to_forelem(spec, ["k", "v"])
    mr = forelem_to_mapreduce(prog)
    got = _run_exported(mr, s.db["t"])
    assert got == sorted(r.rows)


def test_export_mr_rejects_non_mr_shape():
    p = sql_to_forelem("SELECT k FROM t WHERE k > 1", {"t": ["k"]})
    with pytest.raises(NotMapReduceShape):
        forelem_to_mapreduce(p)


def test_export_mr_canonicalized_program_roundtrip():
    # canonicalization must not break the two-adjacent-loop shape detection
    prog = canonicalize_array_names(
        mapreduce_to_forelem(MapReduceSpec("t", "k", FieldRef("t", "i", "v")), ["k", "v"])
    )
    mr = forelem_to_mapreduce(prog)
    assert mr.table == "t"


# ---------------------------------------------------------------------------
# Results surface
# ---------------------------------------------------------------------------


def test_scalar_and_ordered_results(web_session):
    s, urls, lat = web_session
    r = s.sql("SELECT SUM(latency) FROM access WHERE url = 3")
    assert r.scalar() == pytest.approx(float(lat[urls == 3].sum()), rel=1e-4)
    top = s.sql("SELECT url, COUNT(url) AS c FROM access GROUP BY url ORDER BY c DESC LIMIT 3")
    counts = sorted(np.unique(urls, return_counts=True)[1], reverse=True)[:3]
    assert [c for _, c in top.rows] == [int(c) for c in counts]


# ---------------------------------------------------------------------------
# end to end: data → IR → optimize (reformat + parallelize) → execute
# ---------------------------------------------------------------------------


def test_full_bigdata_session():
    """SQL session over weblogs: optimize (reformat+parallelize) and check
    answers against numpy on the raw strings."""
    from repro.core import OptimizeOptions, optimize
    from repro.data.multiset import Database, Multiset, PlainColumn
    from repro.frontends.sql import sql_to_forelem

    rng = np.random.default_rng(0)
    n = 20_000
    urls = np.array([f"http://s{u%31}.com" for u in rng.zipf(1.5, n) % 500], dtype=object)
    status = rng.choice([200, 404, 500], n).astype(np.int32)
    db = Database().add(Multiset("logs", {"url": PlainColumn(urls), "status": PlainColumn(status)}))
    schemas = {"logs": ["url", "status"]}

    res = optimize(sql_to_forelem("SELECT status, COUNT(status) FROM logs GROUP BY status", schemas),
                   db, OptimizeOptions(n_parts=4))
    got = dict(res.plan.run()["R"])
    vals, counts = np.unique(status, return_counts=True)
    assert got == {int(v): int(c) for v, c in zip(vals, counts)}

    res2 = optimize(sql_to_forelem("SELECT SUM(status) FROM logs WHERE status = 500", schemas),
                    res.db, OptimizeOptions(n_parts=1, reformat=False))
    assert res2.plan.run()["scalar"] == int(status[status == 500].sum())
