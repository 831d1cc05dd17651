"""The ``bdb.agg_small.mesh4`` cell as ``BENCHMARK.json`` and its files
declare it, run end to end on four host devices at a tiny size: one
untraced and one traced run in a child process (JAX reads the device count
when it starts).  Then its device-trace readers on a hand-made summary."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.harness import LayerContext  # noqa: E402
from bench.tests._tiny import V5E  # noqa: E402
from bench.trace_reduce import TraceSummary  # noqa: E402
from bench.traffic import Request  # noqa: E402

CELL = "bdb.agg_small.mesh4"
BENCH = spec.load_benchmark(ROOT)

CHILD = r"""
import json, sys, tempfile, time
sys.path[:0] = [".", "src"]
from pathlib import Path
from bench import spec
from bench.harness import run_cell
from bench.tests._tiny import CPU_DEVICE, V5E
from repro.backends.jax_vec import Plan

placed = set()
real = Plan.input_columns
def input_columns(self):
    cols = real(self)
    placed.update((k, self.n_devices) for k in self.upload_bytes)
    return cols
Plan.input_columns = input_columns

bench = spec.load_benchmark()
cfg = spec.config(bench, spec.workload(bench, "bdb.agg_small.mesh4")["config"])
# tiny, yet large enough that the planner splits the table over the mesh
cfg.update(uservisits_rows=(1 << 18) + 6, rankings_rows=1 << 15)
out = {}
for trace in (False, True):
    with tempfile.TemporaryDirectory() as d:
        out[str(trace)] = run_cell(
            bench, "bdb.agg_small.mesh4", 2**31 + 4099, 1.0, trace, t_process=time.perf_counter(),
            device=dict(CPU_DEVICE, count=4, chips_used=4), peaks=V5E,
            trace_dir=Path(d) / "t", config_override=cfg, log=lambda s: None)
out["placed"] = sorted(placed)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


def test_cell_declared_on_four_chips():
    cell = spec.workload(BENCH, CELL)
    assert cell["chips"] == 4
    cfg = spec.config(BENCH, cell["config"])
    one = spec.config(BENCH, "bdb_uservisits")
    assert cfg["uservisits_rows"] == 4 * one["uservisits_rows"]
    assert cfg["rankings_rows"] == 4 * one["rankings_rows"]
    assert (cfg["uservisits"], cfg["rankings"], cfg["generator"]) == \
        (one["uservisits"], one["rankings"], one["generator"])
    traffic = spec.traffic(cell["traffic"])
    assert traffic["entry"]["options"] == {"revalidate": "signature", "mesh": True, "n_parts": 4}
    assert traffic["check"] == spec.traffic("bdb_agg_small")["check"]


@pytest.mark.parametrize("trace", ["False", "True"])
def test_cell_runs_correct_on_four_devices(runs, trace):
    out = runs[trace]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4


def test_columns_go_to_the_four_devices_as_shards(runs):
    assert runs["placed"] == [["sharded", 4]]


def test_untraced_run_reports_qps_and_setup(runs):
    want = {m["name"] for m in spec.cell_metrics(BENCH, CELL, "end_to_end")}
    assert want == {"qps", "setup_s"}
    assert set(runs["False"]["metrics"]) == want


@pytest.mark.parametrize("metric", ["upload_ms.mesh", "upload_gbps.mesh", "compute_ms.mesh"])
def test_traced_run_reports_span_and_counter_readers(runs, metric):
    assert {m["name"] for m in spec.cell_metrics(BENCH, CELL, "per_layer")} >= {metric}
    assert runs["True"]["metrics"][metric]["value"] > 0


def test_cpu_trace_leaves_device_readers_silent(runs):
    # the CPU trace has no TPU plane
    got = runs["True"]["metrics"]
    for m in ("collective_ms.mesh", "groupby_roofline.mesh", "device_idle_share.mesh"):
        assert m not in got


def _ctx(device):
    n = 1 << 20
    tables = {"uservisits": {"ip7": np.arange(n, dtype=np.int32) % 2048,
                             "adRevenue": np.ones(n, np.float32)}}
    t = {"name": "agg", "query": {"from": [["uservisits", None]], "group_by": "ip7",
                                  "select": ["ip7", ["sum", "adRevenue"]]}}
    return LayerContext(n_queries=4, device=device, tables=tables, peaks=V5E,
                        executed=[Request(i, t, {}) for i in range(4)],
                        counters={"upload.bytes{placement=sharded}": 4 * 8 * n})


def test_device_readers_on_a_four_chip_summary():
    dev = TraceSummary(window_s=2.0, busy_s=1.5, compute_s=1.2, n_devices=4,
                       op_s={"%psum.14": 0.002, "%all-reduce.1": 0.001,
                             "%segreduce tpu_custom_call": 1.1, "%fusion": 0.097})
    ctx = _ctx(dev)
    assert spec.metric_reader("collective_ms.mesh")(ctx) == pytest.approx(1e3 * 0.003 / 4)
    assert spec.metric_reader("device_idle_share.mesh")(ctx) == pytest.approx(25.0)
    one_chip = spec.metric_reader("groupby_roofline")(ctx)
    need = 4 * ((1 << 20) * 8 + 2048 * 4) / 819e9
    assert one_chip == pytest.approx(100 * need / 1.2)
    assert spec.metric_reader("groupby_roofline.mesh")(ctx) == pytest.approx(one_chip / 4)


def test_readers_silent_without_what_they_read():
    ctx = _ctx(None)
    for m in ("collective_ms.mesh", "groupby_roofline.mesh", "device_idle_share.mesh",
              "upload_ms.mesh", "compute_ms.mesh"):
        assert spec.metric_reader(m)(ctx) is None
    # bytes but no upload span (the program's spans were not collected)
    assert spec.metric_reader("upload_gbps.mesh")(ctx) is None
