# Reformatting (§III-C1) invariants: dictionary encoding, range
# compression, the reformat planner and its amortization gate.
import numpy as np

from repro.core import OptimizeOptions, optimize
from repro.core.reformat import apply_reformat, plan_reformat
from repro.data.multiset import (
    CompressedRangeColumn,
    Database,
    DictColumn,
    Multiset,
    PlainColumn,
    dict_encode,
)
from repro.frontends.sql import sql_to_forelem


# ---------------------------------------------------------------------------
# reformatting
# ---------------------------------------------------------------------------


def test_dict_encode_roundtrip(rng):
    vals = np.array([f"s{i%7}" for i in rng.integers(0, 100, 200)], dtype=object)
    col = dict_encode(vals)
    assert col.num_keys == len(np.unique(vals))
    assert (col.decode() == vals).all()


def test_compressed_range_column():
    ms = Multiset.from_columns("t", ts=np.arange(10, 1000, 3, dtype=np.int64), x=np.zeros(330, np.int32))
    c = ms.reformat_compress_ranges()
    assert isinstance(c.columns["ts"], CompressedRangeColumn)
    np.testing.assert_array_equal(c.field("ts"), ms.field("ts"))
    assert c.columns["ts"].nbytes < ms.columns["ts"].nbytes


def test_reformat_planner_prunes_and_encodes(rng):
    urls = np.array([f"u{i%9}" for i in range(500)], dtype=object)
    db = Database().add(Multiset("logs", {
        "url": PlainColumn(urls),
        "unused": PlainColumn(rng.integers(0, 10, 500)),
    }))
    prog = sql_to_forelem("SELECT url, COUNT(url) FROM logs GROUP BY url", {"logs": ["url", "unused"]})
    plan = plan_reformat(prog, db)
    actions = {a.action for a in plan.actions}
    assert "prune" in actions and "dict_encode" in actions
    db2 = apply_reformat(plan, db)
    assert "unused" not in db2["logs"].field_names()
    assert isinstance(db2["logs"].columns["url"], DictColumn)
    assert db2["logs"].nbytes < db["logs"].nbytes


def test_amortization_gate():
    # repetitive strings: dictionary encoding shrinks the column -> pays off
    urls = np.array([f"http://long-host-name-{i % 10}.example.com/path" for i in range(2000)], dtype=object)
    db = Database().add(Multiset("t", {"url": PlainColumn(urls)}))
    prog = sql_to_forelem("SELECT url, COUNT(url) FROM t GROUP BY url", {"t": ["url"]})
    plan = plan_reformat(prog, db)
    assert plan.per_run_bytes_saved > 0
    assert plan.worthwhile(expected_runs=1000)
    assert plan.oneoff_bytes > 0

    # all-unique strings: encoding does not shrink -> planner reports no
    # per-run saving (the paper's 'prohibitively expensive' case)
    uniq = np.array([f"u{i}" for i in range(100)], dtype=object)
    db2 = Database().add(Multiset("t", {"url": PlainColumn(uniq)}))
    plan2 = plan_reformat(prog, db2)
    assert plan2.per_run_bytes_saved == 0


def test_optimize_reformats_then_answers_match_python(rng):
    urls = np.array([f"http://h{i%13}/p" for i in rng.integers(0, 300, 2000)], dtype=object)
    db = Database().add(Multiset("access", {"url": PlainColumn(urls)}))
    prog = sql_to_forelem("SELECT url, COUNT(url) FROM access GROUP BY url", {"access": ["url"]})
    res = optimize(prog, db, OptimizeOptions(n_parts=4))
    got = res.plan.run()["R"]
    # decode integer keys back to strings and compare against numpy
    dcol = res.db["access"].columns["url"]
    want = {u: c for u, c in zip(*np.unique(urls, return_counts=True))}
    for code, count in got:
        assert want[dcol.dictionary[code]] == count
