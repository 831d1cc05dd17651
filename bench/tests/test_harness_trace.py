"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (four GROUP BYs of 2^18 rows under harness annotations)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402
from bench.trace_reduce import IDLE, Event, gaps, op_name, reduce_file, self_times, summarize, union  # noqa: E402

CHIP_TRACE = Path(__file__).parent / "data" / "v5e_agg_small.xplane.pb"


def test_union_and_gaps():
    merged = union([(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (4.0, 5.0)])
    assert merged == [(0.0, 2.0), (3.0, 5.0)]
    assert gaps(merged, -1.0, 6.0) == [(-1.0, 0.0), (2.0, 3.0), (5.0, 6.0)]


def test_summary_busy_idle_ops_and_gap_attribution():
    ops = [Event("while", 1.0, 4.0), Event("body", 1.5, 3.5), Event("k", 6.0, 7.0)]
    host = [Event("bench.window", 0.0, 10.0), Event("bench.q.a", 0.5, 5.0),
            Event("bench.q.b", 5.5, 9.0)]
    s = summarize({"/device:TPU:0": ops}, host)
    assert s.window_s == pytest.approx(10.0)
    assert s.busy_s == pytest.approx(4.0)
    assert s.idle_share == pytest.approx(0.6)
    assert s.op_s == pytest.approx({"while": 1.0, "body": 2.0, "k": 1.0})
    # gaps: [0,1] mid 0.5 -> a; [4,6] mid 5 -> a (ends at 5); [7,10] mid 8.5 -> b
    assert s.idle_gaps == pytest.approx({"bench.q.a": 3.0, "bench.q.b": 3.0})
    s2 = summarize({"/device:TPU:0": ops}, [Event("bench.window", 0.0, 10.0)])
    assert s2.idle_gaps == pytest.approx({IDLE: 6.0})


def test_self_times_clip_to_window():
    ops = [Event("outer", 0.0, 10.0), Event("inner", 2.0, 4.0)]
    assert self_times(ops, 3.0, 8.0) == pytest.approx({"outer": 4.0, "inner": 1.0})


def test_op_name_keeps_kernel_target():
    full = ('%_fused_impl.1 = (f32[16,128]) custom-call(s32[8192,128] %b), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert op_name(full) == "%_fused_impl.1 tpu_custom_call"
    assert op_name("%iota.1 = s32[2048]{0} iota(), iota_dimension=0") == "%iota.1"


@pytest.fixture(scope="module")
def chip_summary():
    return reduce_file(str(CHIP_TRACE))


def test_chip_trace_reduces(chip_summary):
    s = chip_summary
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.compute_s == pytest.approx(s.busy_s)
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)
    top, secs = s.top_ops(1)[0]
    assert top.endswith("tpu_custom_call") and secs > 0.5 * s.busy_s
    assert set(s.idle_gaps) <= {"bench.q.agg", IDLE}
    assert sum(s.idle_gaps.values()) == pytest.approx(s.window_s - s.busy_s)


def test_groupby_roofline_arithmetic(chip_summary):
    """Four GROUP BYs of 2^18 rows (int32 key, f32 value, 2,048 keys): the
    reader's share is four times the least time of the needed bytes at HBM
    peak, over the device's compute time."""
    import numpy as np

    from bench.harness import LayerContext
    from bench.tests._tiny import V5E
    from bench.traffic import Request

    n = 1 << 18
    tables = {"uservisits": {"ip7": np.arange(n, dtype=np.int32) % 2048,
                             "adRevenue": np.ones(n, np.float32)}}
    t = {"name": "agg", "query": {"from": [["uservisits", None]], "group_by": "ip7",
                                  "select": ["ip7", ["sum", "adRevenue"]]}}
    ctx = LayerContext(n_queries=4, device=chip_summary, tables=tables, peaks=V5E,
                       executed=[Request(i, t, {}) for i in range(4)])
    need = 4 * (n * 4 + n * 4 + 2048 * 4) / 819e9
    got = spec.metric_reader("groupby_roofline")(ctx)
    assert got == pytest.approx(100 * need / chip_summary.compute_s)
    assert 0 < got < 100
    ctx.device = None
    assert spec.metric_reader("groupby_roofline")(ctx) is None
