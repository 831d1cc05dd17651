# Device-resident table columns (backends/resident.py): a column that a
# session-dispatched monolithic plan placed on the device is read from there
# by later plans, until its table is replaced, dropped or edited.  Every
# case checks the answers against the reference interpreter, and what each
# run placed against what it read resident.
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from ab_tables import ref_rows
from repro.backends.jax_vec import Plan
from repro.backends.resident import resident_columns
from repro.core import OptimizeOptions, optimize
from repro.data.multiset import Database, Multiset
from repro.engine import QueryServer, Session
from repro.frontends.mapreduce import MapReduceSpec
from repro.frontends.sql import sql_to_forelem

N = 1000
COL = N * 4  # bytes of one int32 column
SCHEMA = {"t": ["k", "v", "w"]}
SUM_V = "SELECT k, SUM(v) FROM t GROUP BY k"
SUM_W = "SELECT k, SUM(w) FROM t GROUP BY k"


def _cols(seed):
    rng = np.random.default_rng(2**31 + seed)
    return {"k": rng.integers(0, 17, N).astype(np.int32),
            "v": rng.integers(-50, 50, N).astype(np.int32),
            "w": rng.integers(0, 9, N).astype(np.int32)}


def _check(qr, db, sql):
    assert sorted(qr.rows) == ref_rows(sql_to_forelem(sql, SCHEMA), db)


def _moved(qr):
    """(bytes placed, bytes read resident) by the query's run."""
    return qr.plan.upload_bytes, qr.plan.resident_bytes


def test_second_query_places_nothing():
    s = Session(revalidate="signature")
    s.register("t", **_cols(1))
    first = s.sql(SUM_V)
    _check(first, s.db, SUM_V)
    assert _moved(first) == ({"single": 2 * COL}, {})
    # the MapReduce form of the same query reads the same two columns
    again = s.mapreduce(MapReduceSpec.aggregate("t", "k", "v"))
    assert sorted(again.rows) == sorted(first.rows)
    assert _moved(again) == ({}, {"single": 2 * COL})
    c = s.metrics()["counters"]
    assert c["upload.bytes{placement=single}"] == 2 * COL
    assert c["resident.bytes{placement=single}"] == 2 * COL
    assert c["columns.resident{result=miss}"] == 2
    assert c["columns.resident{result=hit}"] == 2
    assert len(resident_columns(s.db)) == 2


@pytest.mark.parametrize("how", ["replace", "drop_register"])
def test_a_new_table_is_placed_again(how):
    s = Session(revalidate="signature")
    s.register("t", **_cols(2))
    old = s.sql(SUM_V)
    if how == "drop_register":
        s.drop("t")
    s.register("t", **_cols(3))
    new = s.sql(SUM_V)
    _check(new, s.db, SUM_V)
    assert sorted(new.rows) != sorted(old.rows)
    assert _moved(new) == ({"single": 2 * COL}, {})


@pytest.mark.parametrize("reader", ["same_session", "session_made_after_the_edit"])
def test_in_place_edit_is_placed_again_under_content_revalidation(reader):
    cols = _cols(4)
    s = Session(revalidate="content")
    s.register("t", **cols)
    assert np.shares_memory(s.db["t"].field("v"), cols["v"])  # the session holds the array
    s.sql(SUM_V)
    cols["v"][: N // 2] += 1000
    if reader != "same_session":
        s = Session(s.db, revalidate="content")  # it never saw the old epoch
    edited = s.sql(SUM_V)
    _check(edited, s.db, SUM_V)
    assert _moved(edited) == ({"single": 2 * COL}, {})


def test_tenants_of_one_server_share_one_copy():
    srv = QueryServer(backend="jax")
    srv.register("t", **_cols(5))
    try:
        a = srv.submit(SUM_V, tenant="a")
        assert _moved(a) == ({"single": 2 * COL}, {})
        b = srv.submit(SUM_V, tenant="b")
        _check(b, srv.db, SUM_V)
        assert _moved(b) == ({}, {"single": 2 * COL})
        assert srv.session("a").db is srv.session("b").db
        c = srv.metrics.snapshot()["counters"]
        assert c["upload.bytes{placement=single}"] == 2 * COL
    finally:
        srv.close()


def test_concurrent_tenants_place_each_column_once(monkeypatch):
    placed = []
    real = Plan._place

    def place(self, ms, field, sharded):
        placed.append(field)
        return real(self, ms, field, sharded)

    monkeypatch.setattr(Plan, "_place", place)
    # four queries over the same two columns: distinct texts, so their first
    # runs are not serialised by the server's single-flight compile
    queries = [f"SELECT k, {op}(v) FROM t GROUP BY k" for op in ("SUM", "MIN", "MAX", "COUNT")]
    srv = QueryServer(backend="jax", max_pending=64, admission="block")
    srv.register("t", **_cols(8))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(16) as ex:
            futs = [ex.submit(srv.submit, queries[i % 4], tenant=f"t{i % 3}") for i in range(32)]
            got = [(queries[i % 4], f.result(timeout=120)) for i, f in enumerate(futs)]
    finally:
        sys.setswitchinterval(interval)
        srv.close()
    for sql, qr in got:
        _check(qr, srv.db, sql)
    assert sorted(placed) == ["k", "v"]


def test_least_recently_used_column_goes_past_the_budget():
    s = Session(revalidate="signature")
    s.register("t", **_cols(6))
    store = resident_columns(s.db)
    store._budget = 2 * COL  # two columns a device
    _check(s.sql(SUM_V), s.db, SUM_V)  # k, v resident
    w = s.sql(SUM_W)  # k read resident; w placed, v (not read) evicted
    _check(w, s.db, SUM_W)
    assert _moved(w) == ({"single": COL}, {"single": COL})
    v = s.sql(SUM_V)  # v placed again, w evicted
    _check(v, s.db, SUM_V)
    assert _moved(v) == ({"single": COL}, {"single": COL})
    assert len(store) == 2
    # a budget under one query's columns keeps what fits, and the answer
    store.clear()
    store._budget = COL
    for _ in range(2):
        _check(s.sql(SUM_V), s.db, SUM_V)
        assert len(store) == 1


def test_plan_outside_a_session_places_every_run():
    cols = _cols(7)
    db = Database().add(Multiset.from_columns("t", **cols))
    plan = optimize(sql_to_forelem(SUM_V, SCHEMA), db, OptimizeOptions()).plan
    assert plan.resident is None
    for _ in range(2):
        rows = plan.run()["R"]
        assert sorted(rows) == ref_rows(sql_to_forelem(SUM_V, SCHEMA), db)
        assert (plan.upload_bytes, plan.resident_bytes) == ({"single": 2 * COL}, {})
