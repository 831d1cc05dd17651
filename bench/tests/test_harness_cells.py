"""Each cell run end to end at a tiny size on the CPU, past the harness's
look for a chip: the configuration's generator, the engine entry the
traffic names and the reference agree, and the result line has the
contract's keys.  Also the pieces: SQL rendering, the reference on a table
small enough to check by hand, and the traffic generator's fixed counts."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import query, reference, spec  # noqa: E402
from bench import traffic as tr  # noqa: E402
from bench.tests._tiny import CELLS, run_tiny, tiny_config  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
BIG_SEED = 2**31 + 977


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "1")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_correct_at_tiny_size(cell, pallas_interpret):
    out = run_tiny(BENCH, cell, BIG_SEED)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in spec.cell_metrics(BENCH, cell, "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())


def test_traced_run_reports_program_layers(pallas_interpret, tmp_path):
    out = run_tiny(BENCH, "bdb.agg_small.batch", 5, trace=True, trace_dir=tmp_path / "t")
    assert out["correct"]
    # the CPU trace has no TPU plane, so only span readers report
    assert {"plan_ms.batch", "upload_ms.batch", "compute_ms.batch", "densify_ms.batch"} \
        <= set(out["metrics"])
    assert "device_idle_share.batch" not in out["metrics"]


def test_sql_rendering():
    t = spec.traffic("bdb_q3a")["templates"][0]["query"]
    assert query.to_sql(t) == (
        "SELECT uv.sourceIP, SUM(uv.adRevenue), AVG(r.pageRank) FROM rankings r, uservisits uv "
        "WHERE r.pageURL = uv.destURL AND uv.visitDate >= 3652 AND uv.visitDate <= 3743 "
        "GROUP BY uv.sourceIP ORDER BY SUM(uv.adRevenue) DESC LIMIT 1")
    agg = spec.traffic("bdb_agg_small")["templates"][0]["query"]
    assert query.mapreduce_args(agg) == ("uservisits", "ip7", "adRevenue", "+")


def test_reference_by_hand():
    tables = {"t": {"k": np.array([2, 0, 2, 5], np.int32),
                    "v": np.array([1.5, 2.0, 0.5, 4.0], np.float32)},
              "d": {"id": np.array([0, 2, 5], np.int32), "w": np.array([10, 20, 30], np.int32)}}
    q = {"from": [["t", None]], "where": [["v", ">=", ":lo"]], "group_by": "k",
         "select": ["k", ["sum", "v * 2"], ["count", "v"]]}
    a = reference.evaluate(q, tables, {"lo": np.float32(1.0)})
    assert a.keys.tolist() == [0, 2, 5]
    assert a.values.tolist() == [[4.0, 1.0], [3.0, 1.0], [8.0, 1.0]]
    j = {"from": [["d", "d"], ["t", "t"]], "join": ["d.id", "t.k"], "group_by": "t.k",
         "select": ["t.k", ["sum", "t.v"], ["avg", "d.w"]], "order_by": [1, "desc"], "limit": 1}
    top = reference.returned_rows(j, reference.evaluate(j, tables, {}))
    assert top.keys.tolist() == [5] and top.values.tolist() == [[4.0, 30.0]]


def test_discount_parameters_match_the_generated_column():
    """Q6's discount bounds are compared with an f32 column made as k / 100:
    the JSON values must round to the same floats."""
    cfg = tiny_config(BENCH, "tpch.mix.serve")
    col = np.unique(spec.generator(cfg).generate(cfg, 3)["lineitem"]["l_discount"])
    assert len(col) == 11
    q6 = spec.traffic("tpch_q6_q15")["templates"][0]
    for lo, hi in q6["params"][1]["values"]:
        assert np.float32(lo) in col and np.float32(hi) in col


def test_generators_are_seeded():
    for cell in ("bdb.agg_small.batch", "tpch.mix.serve"):
        cfg = tiny_config(BENCH, cell)
        gen = spec.generator(cfg)
        a, b, c = gen.generate(cfg, BIG_SEED), gen.generate(cfg, BIG_SEED), gen.generate(cfg, 7)
        for t in a:
            for col in a[t]:
                assert np.array_equal(a[t][col], b[t][col]), (t, col)
                assert a[t][col].dtype in (np.int32, np.float32) and a[t][col].flags.c_contiguous
        assert any(not np.array_equal(a[t][col], c[t][col]) for t in a for col in a[t])


def test_open_schedule_fixed_arrivals_and_seeded_tenants():
    traffic = spec.traffic("tpch_q6_q15")
    a = tr.open_schedule(traffic, 1, 20.0, rate_qps=2.0)
    b = tr.open_schedule(traffic, 2**31 + 5, 20.0, rate_qps=2.0)
    assert len(a) == len(b) == 40
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert [r.template["name"] for r in a] == [r.template["name"] for r in b]
    assert 0 == a[0].due_s and a[-1].due_s < 20.0
    assert sorted(np.diff([r.due_s for r in a])) == pytest.approx(
        sorted(-np.log1p(-(np.arange(39) + 0.5) / 39) * (20.0 * 39 / 40) / (
            -np.log1p(-(np.arange(39) + 0.5) / 39)).sum()))
    names = [r.template["name"] for r in a]
    assert names.count("tpch_q6") == names.count("tpch_q15_revenue") == 20
    tenants = sorted(r.tenant for r in a)
    assert tenants == sorted(r.tenant for r in b) and tenants.count("t0") == 16
    assert [r.tenant for r in a] != [r.tenant for r in b]
    again = tr.open_schedule(traffic, 1, 20.0, rate_qps=2.0)
    assert [(r.due_s, r.tenant, r.params) for r in again] == [(r.due_s, r.tenant, r.params) for r in a]
