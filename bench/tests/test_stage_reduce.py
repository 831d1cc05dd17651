"""The reduction by engine stage and device program: on hand-made events, on
the committed v5e trace, and on a trace recorded here on the CPU with the
engine's tracer on."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec, stage_reduce  # noqa: E402
from bench.trace_reduce import IDLE, Event, reduce_file  # noqa: E402

CHIP_TRACE = Path(__file__).parent / "data" / "v5e_agg_small.xplane.pb"


def test_module_name_drops_prefix_and_hash():
    assert stage_reduce.module_name("jit_chunk_agg(10312508987229598080)") == "chunk_agg"
    assert stage_reduce.module_name("jit_q_groupby") == "q_groupby"
    assert stage_reduce.module_name("fft(12)") == "fft"


def test_gaps_named_by_stage_and_ops_by_module():
    dev = "/device:TPU:0"
    ops = [Event("%fusion", 1.0, 2.0), Event("%segreduce.1", 4.0, 5.0), Event("%add", 8.0, 8.5)]
    modules = [Event("q_groupby", 0.9, 5.1), Event("add", 7.9, 8.6)]
    host = [
        Event("bench.window", 0.0, 10.0),
        Event("bench.q.a", 0.5, 6.0),
        Event("repro.query", 0.6, 5.9),
        Event("repro.jax.upload", 2.0, 3.9),      # gap [2, 4]: mid 3 -> upload
        Event("repro.densify", 5.0, 5.9),         # gap [5, 8]: mid 6.5 -> outside a
        Event("bench.q.b", 6.2, 9.0),
        Event("repro.merge", 8.5, 9.0),           # gap [8.5, 10]: mid 9.25 -> no query
    ]
    s = stage_reduce.summarize({dev: ops}, {dev: modules}, host)
    assert s.base.busy_s == pytest.approx(2.5)
    assert s.idle_gaps == pytest.approx({
        "bench.q.a": 1.0,             # [0, 1]: mid 0.5, before any stage opens
        "bench.q.a/jax.upload": 2.0,  # [2, 4]
        "bench.q.b": 3.0,             # [5, 8]: mid 6.5, inside b, no stage open
        IDLE: 1.5,                    # [8.5, 10]: mid 9.25
    })
    assert s.in_query_idle_s() == pytest.approx(6.0)
    assert s.named_idle_s() == pytest.approx(2.0)
    assert s.op_s == pytest.approx({"q_groupby/%fusion": 1.0, "q_groupby/%segreduce.1": 1.0,
                                    "add/%add": 0.5})
    assert s.module_s == pytest.approx({"q_groupby": 4.2, "add": 0.7})


def test_coarse_stages_are_not_named_idle():
    dev = "/device:TPU:0"
    host = [Event("bench.window", 0.0, 4.0), Event("bench.q.a", 0.0, 4.0),
            Event("repro.query", 0.0, 4.0), Event("repro.execute", 2.0, 4.0)]
    s = stage_reduce.summarize({dev: [Event("%f", 1.0, 2.0)]}, {dev: []}, host)
    assert s.idle_gaps == pytest.approx({"bench.q.a/query": 1.0, "bench.q.a/execute": 2.0})
    assert s.named_idle_s() == 0.0
    assert s.op_s == pytest.approx({"?/%f": 1.0})


def test_latest_starting_open_span_across_threads_wins():
    dev = "/device:TPU:0"
    host = [Event("bench.window", 0.0, 10.0), Event("bench.q.a", 0.0, 10.0),
            Event("repro.dispatch", 1.0, 9.0),   # a worker thread's chunk
            Event("repro.merge", 2.0, 3.0),      # closed before the gap's midpoint
            Event("repro.densify", 4.0, 8.0)]    # the query's thread, started later
    s = stage_reduce.summarize({dev: [Event("%f", 0.0, 4.0), Event("%g", 8.0, 10.0)]},
                               {dev: []}, host)
    assert s.idle_gaps == pytest.approx({"bench.q.a/densify": 4.0})


def test_v5e_trace_keeps_the_accepted_readings():
    """The committed trace reads as it did before the stage reduction existed,
    whichever reduction reads it."""
    base = reduce_file(str(CHIP_TRACE))
    st = stage_reduce.reduce_file(str(CHIP_TRACE))
    for s in (base, st.base):
        assert s.busy_s == pytest.approx(0.002220646, rel=1e-6)
        assert s.compute_s == pytest.approx(0.002220646, rel=1e-6)
        assert s.window_s == pytest.approx(0.057223847, rel=1e-6)
        assert s.idle_share == pytest.approx(0.961193696, rel=1e-6)
    assert st.base == base
    # recorded before programs were named: every module is ``jit_run``
    assert set(st.module_s) == {"run"}
    assert st.top_ops(1)[0][0] == "run/%_fused_impl.1 tpu_custom_call"
    assert sum(st.op_s.values()) == pytest.approx(base.busy_s)
    # the trace holds no engine spans, so gaps keep the query's name
    assert st.idle_gaps == pytest.approx(base.idle_gaps)


def test_v5e_trace_groupby_roofline_pinned():
    import numpy as np

    from bench.harness import LayerContext
    from bench.tests._tiny import V5E
    from bench.traffic import Request

    n = 1 << 18
    tables = {"uservisits": {"ip7": np.arange(n, dtype=np.int32) % 2048,
                             "adRevenue": np.ones(n, np.float32)}}
    t = {"name": "agg", "query": {"from": [["uservisits", None]], "group_by": "ip7",
                                  "select": ["ip7", ["sum", "adRevenue"]]}}
    for device in (reduce_file(str(CHIP_TRACE)), stage_reduce.reduce_file(str(CHIP_TRACE)).base):
        ctx = LayerContext(n_queries=4, device=device, tables=tables, peaks=V5E,
                           executed=[Request(i, t, {}) for i in range(4)])
        assert spec.metric_reader("groupby_roofline")(ctx) == pytest.approx(0.4630414023, rel=1e-6)


@pytest.mark.parametrize("backend", ["jax", "partitioned"])
def test_engine_spans_on_the_profiler_clock(tmp_path, backend):
    """A CPU trace holds each ``repro.obs`` span as a ``repro.*`` host event,
    nested inside the harness's query annotation, as long as the span."""
    import jax
    import numpy as np

    from repro import Session

    rng = np.random.default_rng(0)
    s = Session(backend=backend, trace=True)
    s.register("t", k=rng.integers(0, 64, 50_000).astype(np.int32),
               v=rng.random(50_000).astype(np.float32))
    q = "SELECT k, SUM(v) FROM t GROUP BY k"
    s.sql(q)
    s.take_trace()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.q.t"):
        s.sql(q)
    jax.profiler.stop_trace()
    spans = s.take_trace().spans
    _, _, host = stage_reduce.load(str(sorted(tmp_path.glob("**/*.xplane.pb"))[-1]))
    (q_ev,) = [e for e in host if e.name == "bench.q.t"]
    stages = ["densify", "jax.upload"] if backend == "jax" else ["densify", "merge", "dispatch"]
    for name in stages:
        evs = [e for e in host if e.name == "repro." + name]
        sps = [sp for sp in spans if sp.name == name]
        assert evs and len(evs) == len(sps), name
        for e in evs:
            assert q_ev.start <= e.start and e.end <= q_ev.end
        ev_ms = sorted(1e3 * (e.end - e.start) for e in evs)
        sp_ms = sorted(sp.dur_ms for sp in sps)
        for a, b in zip(ev_ms, sp_ms):
            assert abs(a - b) <= max(0.05 * b, 0.2), (name, a, b)
