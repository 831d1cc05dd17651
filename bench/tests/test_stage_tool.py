"""The chunk time split on the served cell, and the stage breakdown tool,
run at a tiny size on the CPU."""
import importlib.util
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.tests._tiny import run_tiny, tiny_config  # noqa: E402

BENCH = spec.load_benchmark(ROOT)
SPLIT = ("chunk_host_ms.serve", "chunk_ready_ms.serve")


def _tool():
    path = ROOT / "bench" / "tools" / "stages.py"
    mod_spec = importlib.util.spec_from_file_location("bench_tool_stages", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def test_traced_serve_run_splits_chunk_time(tmp_path):
    out = run_tiny(BENCH, "tpch.mix.serve", 2**31 + 5, trace=True, trace_dir=tmp_path / "t")
    assert out["correct"], out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(SPLIT) <= set(m)
    assert m["chunk_host_ms.serve"] > 0 and m["chunk_ready_ms.serve"] >= 0
    # worker.host_ms + worker.ready_ms == worker.busy_ms, each per query
    assert m["chunk_host_ms.serve"] + m["chunk_ready_ms.serve"] == \
        pytest.approx(m["chunk_busy_ms.serve"], rel=1e-9)
    # no device plane on the CPU, so no device-trace reader reports
    assert "device_idle_share.serve" not in m


def test_split_metrics_stay_silent_without_the_counters():
    """Over a program that keeps no such counter the readers return
    nothing and do not raise."""
    from bench.harness import LayerContext

    ctx = LayerContext(n_queries=3, counters={"worker.busy_ms": 30.0})
    for name in SPLIT:
        assert spec.metric_reader(name)(ctx) is None


@pytest.mark.parametrize("cell", ["tpch.mix.serve", "bdb.agg_small.batch"])
def test_stage_tool_pairs_and_breakdown(tmp_path, cell):
    recs = []
    _tool().run(BENCH, cell, 2**31 + 11, 1.0, 1, 0.5, cfg=tiny_config(BENCH, cell),
                trace_dir=tmp_path / "t", emit=recs.append)
    setup, *pairs, traced = recs
    assert setup["phase"] == "setup" and traced["phase"] == "traced"
    assert [(p["pair"], p["tracer"]) for p in pairs] == [(0, "off"), (0, "on")]
    metric = "latency_p50_ms" if cell == "tpch.mix.serve" else "qps"
    for r in pairs + [traced]:
        assert r["requests"] > 0 and r["failed"] == 0 and r[metric] > 0
    assert pairs[0]["spans"] == 0 and pairs[1]["spans"] > 0
    prog = traced["program"]
    assert prog["densify"] is not None
    if cell == "tpch.mix.serve":
        assert prog["merge"] is not None and prog["worker.host_ms"] is not None
        assert prog["worker.host_ms"] + prog["worker.ready_ms"] == \
            pytest.approx(prog["worker.busy_ms"], rel=1e-9)
        assert traced["per_layer"]["chunk_host_ms.serve"] == pytest.approx(prog["worker.host_ms"])
    else:
        assert prog["jax.upload"] is not None and prog["jax.compute"] is not None
    # the CPU has no device plane: no device time, no stage breakdown
    assert "chunk_device_ms" not in prog and "idle_gaps" not in traced
