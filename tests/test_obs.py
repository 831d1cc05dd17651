# Observability subsystem (repro.obs): span nesting/parentage — including
# cross-thread attachment under the async worker pool — metrics snapshot
# stability, Chrome-trace JSON schema validity, the trace ↔ dispatch_log
# agreement the acceptance criteria require, the bounded query log, and the
# well-formed empty runtime report.
import importlib.util
import json
import os
import sys
import threading

import numpy as np
import pytest

from repro import MetricsRegistry, Session, Tracer
from repro.backends import PartitionedChoices, get_backend
from repro.data.multiset import Database, Multiset
from repro.engine import EngineError
from repro.frontends.sql import sql_to_forelem
from repro.obs import NULL_TRACER, QueryTrace, diff_counters, load_trace
from repro.planner import PlanCache, render_analyze

SCHEMAS = {"t": ["k", "v"]}
Q = "SELECT k, SUM(v) FROM t GROUP BY k"


def _cols(n=20_000, key_range=40, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, key_range, n).astype(np.int32),
        "v": rng.integers(-1000, 1000, n).astype(np.int32),
    }


def _db(n=20_000, seed=0):
    return Database().add(Multiset.from_columns("t", **_cols(n, seed=seed)))


def _session(**kw):
    kw.setdefault("backend", "partitioned")
    kw.setdefault("n_partitions", 5)
    kw.setdefault("schedule", "guided")
    s = Session(**kw)
    s.register("t", **_cols())
    return s


# ---------------------------------------------------------------------------
# span tree: pipeline coverage + nesting
# ---------------------------------------------------------------------------


def test_profile_covers_every_pipeline_stage():
    s = _session()
    with s.profile() as qt:
        s.sql(Q)
    names = {sp.name for sp in qt.spans}
    for stage in ("query", "sql.parse", "canonicalize", "optimize", "passes",
                  "cache.lookup", "plan.enumerate", "lower", "distribute",
                  "execute", "dispatch"):
        assert stage in names, f"missing {stage} span; got {sorted(names)}"
    # one root: the query span; every other span reaches it via parents
    roots = qt.roots()
    assert [r.name for r in roots] == ["query"]
    for sp in qt.spans:
        if sp is roots[0]:
            continue
        chain = qt.ancestors(sp)
        assert chain and chain[-1] is roots[0], f"{sp.name} does not reach the query root"
    # per-chunk spans attach under the per-op dispatch span, not the root
    for d in qt.by_name("dispatch"):
        parent = qt.find(d.parent)
        assert parent is not None and parent.name.startswith("dispatch:")


def test_cache_lookup_span_records_hit_and_miss():
    shared = PlanCache()
    s1 = _session(plan_cache=shared, trace=True)
    s1.sql(Q)
    miss = [sp for sp in s1.take_trace().spans if sp.name == "cache.lookup"]
    assert miss and miss[0].attrs["hit"] is False
    # same arrays → same content epoch → the second session's lookup hits
    s2 = _session(plan_cache=shared, trace=True)
    s2.sql(Q)
    hit = [sp for sp in s2.take_trace().spans if sp.name == "cache.lookup"]
    assert hit and hit[0].attrs["hit"] is True


def test_trace_disabled_by_default_zero_spans_identical_results():
    plain = _session()
    traced = _session(trace=True)
    assert plain.tracer is NULL_TRACER
    r_plain = plain.sql(Q).rows
    r_traced = traced.sql(Q).rows
    assert sorted(r_plain) == sorted(r_traced)
    assert len(plain.take_trace()) == 0
    assert len(traced.take_trace()) > 0


# ---------------------------------------------------------------------------
# async worker pool: cross-thread parentage
# ---------------------------------------------------------------------------


def _pool_plan(db, n_partitions=4):
    p = sql_to_forelem(Q, SCHEMAS)
    return get_backend("partitioned").compile(
        p, db,
        PartitionedChoices(n_partitions=n_partitions, schedule="fixed",
                           jit_chunks=True, async_dispatch=True, n_workers=3),
    )


def test_async_chunk_spans_attach_to_owning_op():
    plan = _pool_plan(_db())
    tr = Tracer()
    plan.run(tracer=tr)
    qt = QueryTrace(tr.drain())
    chunks = qt.by_name("dispatch")
    assert len(chunks) == len(plan.dispatch_log) > 1
    ops = {sp.id: sp for sp in qt.spans if sp.name.startswith("dispatch:")}
    for c in chunks:
        # pool threads have no span stack to inherit from: the explicit
        # parent id must point at the op span whose name carries the op
        op = ops.get(c.parent)
        assert op is not None and op.name == f"dispatch:{c.attrs['op']}"
        assert c.attrs["worker"] in (0, 1, 2)


def test_concurrent_queries_keep_chunk_spans_on_their_own_query():
    tr = Tracer()
    plans = {tag: _pool_plan(_db(seed=i), n_partitions=4 + i)
             for i, tag in enumerate(("A", "B"))}

    def run(tag):
        with tr.span("query", q=tag):
            plans[tag].run(tracer=tr)

    threads = [threading.Thread(target=run, args=(tag,)) for tag in plans]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    qt = QueryTrace(tr.drain())
    per_query = {}
    for c in qt.by_name("dispatch"):
        qroots = [a for a in qt.ancestors(c) if a.name == "query"]
        assert len(qroots) == 1, "chunk span must reach exactly one query root"
        per_query.setdefault(qroots[0].attrs["q"], []).append(c)
    # every chunk landed under the query that dispatched it — counts match
    # each plan's own dispatch log exactly
    assert set(per_query) == {"A", "B"}
    for tag, plan in plans.items():
        assert len(per_query[tag]) == len(plan.dispatch_log)


# ---------------------------------------------------------------------------
# trace ↔ dispatch_log agreement
# ---------------------------------------------------------------------------


def test_dispatch_spans_agree_with_dispatch_log():
    s = _session()
    with s.profile() as qt:
        res = s.sql(Q)
    log = res.plan.dispatch_log
    recs = qt.dispatch_records()
    key = lambda d: (d["op"], d["partition"], d["rows"], d["worker"],
                     d["bucket"], d["compiled"])  # noqa: E731
    log_keys = sorted(key(d.trace_attrs()) for d in log)
    rec_keys = sorted(key(r) for r in recs)
    assert log_keys == rec_keys


def test_report_from_trace_matches_runtime_report():
    s = _session()
    with s.profile() as qt:
        res = s.sql(Q)
    from_log = res.plan.runtime_report()
    from_trace = res.plan.report_from_trace(qt)
    assert from_trace["ran"] and from_log["ran"]
    assert from_trace["n_dispatches"] == from_log["n_dispatches"]
    ops_l = {o["op"]: o for o in from_log["ops"]}
    ops_t = {o["op"]: o for o in from_trace["ops"]}
    assert set(ops_l) == set(ops_t)
    for op in ops_l:
        assert ops_t[op]["n_chunks"] == ops_l[op]["n_chunks"]
        assert ops_t[op]["rows"] == ops_l[op]["rows"]
        assert ops_t[op]["t_ms"] == pytest.approx(ops_l[op]["t_ms"])


def test_explain_analyze_renders_from_trace():
    s = _session()
    txt = s.explain(Q, analyze=True)
    assert "analyze (measured):" in txt
    assert "achieved_imbalance" in txt
    assert "jit cache:" in txt


# ---------------------------------------------------------------------------
# empty runtime report (regression: built-but-never-run / 0-row input)
# ---------------------------------------------------------------------------


def test_runtime_report_well_formed_before_any_run():
    plan = _pool_plan(_db())
    rep = plan.runtime_report()
    assert rep["ran"] is False and rep["n_dispatches"] == 0
    assert rep["ops"] == [] and rep["queue_wait_ms"] == 0.0
    text = render_analyze(rep)   # must not raise, must say why it is empty
    assert "no chunks dispatched" in text


def test_runtime_report_well_formed_on_empty_table():
    db = Database().add(Multiset.from_columns(
        "t", k=np.array([], np.int32), v=np.array([], np.int32)))
    plan = _pool_plan(db)
    out = plan.run()
    assert out["R"] == []
    rep = plan.runtime_report()   # 0-row input: no dispatches, no crash
    assert rep["ran"] is False or rep["n_dispatches"] >= 0
    render_analyze(rep)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metrics_snapshot_stable_across_identical_warm_queries():
    s = _session()
    s.sql(Q)                               # cold: compile + cache fill
    snaps = []
    for _ in range(3):
        s.sql(Q)
        snaps.append(s.metrics())
    d1 = diff_counters(snaps[0], snaps[1])
    d2 = diff_counters(snaps[1], snaps[2])
    # measured-time counters (busy/queue ms) legitimately vary run to run;
    # every discrete counter must advance identically on the warm path
    stable = lambda d: {k: v for k, v in d.items() if not k.endswith("ms")}  # noqa: E731
    assert stable(d1) == stable(d2), f"warm deltas drifted: {d1} vs {d2}"
    assert d1["queries{source=sql}"] == 1
    assert d1["plan_cache.hit"] == 1
    assert d1.get("jit.compiles", 0) == 0   # warm: no fresh XLA compiles
    assert d1["rows.scanned"] == 20_000


def test_metrics_match_plan_and_cache_counters():
    s = _session()
    res = s.sql(Q)
    s.sql(Q)
    m = s.metrics()
    c, g = m["counters"], m["gauges"]
    js = res.plan.jit_stats
    assert c["jit.compiles"] == js.compiles
    assert c["jit.hits"] == js.hits
    st = s.plan_cache.stats()
    assert g["plan_cache.hits"] == st["hits"]
    assert g["plan_cache.misses"] == st["misses"]
    assert c["chunks.dispatched"] == 2 * len(res.plan.dispatch_log)
    assert "query.latency_ms" in m["histograms"]


def test_metrics_registry_rejects_negative_and_shares():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.inc("x", -1)
    s1 = _session(metrics=reg)
    s2 = _session(metrics=reg)
    s1.sql(Q)
    s2.sql(Q)
    assert reg.counter_total("queries") == 2   # both sessions feed one registry


@pytest.mark.parametrize("backend,method", [("jax", "direct"), ("partitioned", "search")])
def test_join_probe_counter_names_the_probe(backend, method):
    # a dense build key is probed through a position table on the
    # monolithic path; chunk joins binary-search each sorted partition
    s = _session(backend=backend)
    s.register("d", id=np.arange(40, dtype=np.int32), w=np.arange(40, dtype=np.int32))
    s.sql("SELECT t.k, SUM(d.w) FROM d, t WHERE d.id = t.k GROUP BY t.k")
    counters = s.metrics_registry.snapshot()["counters"]
    assert counters[f"join.probe{{method={method}}}"] == 1
    assert not any(k.startswith("join.probe") and method not in k for k in counters)


@pytest.mark.parametrize("agg,path", [("SUM", "mxu"), ("MAX", "vpu")])
def test_segreduce_path_counter_names_the_kernel_path(monkeypatch, agg, path):
    # priced as on a TPU, the planner sends a 300-key GROUP BY to the
    # segreduce kernel (interpreted here): a sum group is contracted on the
    # MXU, a max group swept on the VPU, one launch a query
    from repro.planner import cost

    monkeypatch.setattr(cost, "pallas_mode", lambda: "compiled")
    monkeypatch.setenv("REPRO_PALLAS", "1")
    s = Session(backend="jax")
    s.register("t", **_cols(key_range=300))
    for _ in range(2):
        s.sql(f"SELECT k, {agg}(v) FROM t GROUP BY k")
    counters = s.metrics_registry.snapshot()["counters"]
    assert {k: v for k, v in counters.items() if k.startswith("segreduce.path")} == {
        f"segreduce.path{{path={path}}}": 2
    }


def test_table_replacement_counts_invalidations():
    s = _session()
    s.sql(Q)
    s.register("t", **_cols(seed=3))   # replace → old epoch's plans invalid
    assert s.metrics()["counters"]["plan_cache.invalidations"] >= 1


# ---------------------------------------------------------------------------
# Chrome-trace schema + export round-trips
# ---------------------------------------------------------------------------


def test_chrome_trace_schema(tmp_path):
    s = _session()
    with s.profile() as qt:
        s.sql(Q)
    obj = qt.to_chrome()
    # strict JSON (Perfetto rejects Infinity/NaN literals)
    text = json.dumps(obj, allow_nan=False)
    obj = json.loads(text)
    events = obj["traceEvents"]
    assert isinstance(events, list) and events
    assert obj["displayTimeUnit"] in ("ms", "ns")
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == len(qt)
    for e in xs:
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert "span_id" in e["args"] and "cat" in e
    # every tid used by an X event has a thread_name metadata record
    named = {e["tid"] for e in ms if e["name"] == "thread_name"}
    assert {e["tid"] for e in xs} <= named
    assert min(e["ts"] for e in xs) == 0   # rebased to trace start


@pytest.mark.parametrize("fname", ["t.json", "t.json.gz", "t.jsonl", "t.jsonl.gz"])
def test_save_load_round_trip(tmp_path, fname):
    s = _session()
    with s.profile() as qt:
        s.sql(Q)
    path = str(tmp_path / fname)
    qt.save(path)
    back = load_trace(path)
    assert len(back) == len(qt)
    assert sorted(sp.name for sp in back.spans) == sorted(sp.name for sp in qt.spans)
    # the tree survives both formats (ids ride in args for Chrome JSON)
    orig = {sp.id: sp.parent for sp in qt.spans}
    assert {sp.id: sp.parent for sp in back.spans} == orig
    assert len(back.dispatch_records()) == len(qt.dispatch_records())


def test_trace_summary_cli(tmp_path):
    s = _session()
    with s.profile() as qt:
        s.sql(Q)
    path = str(tmp_path / "trace.json.gz")
    qt.save(path)
    spec = importlib.util.spec_from_file_location(
        "trace_summary",
        os.path.join(os.path.dirname(__file__), "..", "scripts", "trace_summary.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["trace_summary"] = mod
    spec.loader.exec_module(mod)
    trace = load_trace(path)
    text = mod.render_summary(trace)
    assert "dispatch" in text and "query" in text and "stage" in text
    assert "chunks=" in mod.render_dispatch(trace)
    assert mod.main([path, "--dispatch"]) == 0


# ---------------------------------------------------------------------------
# bounded query log
# ---------------------------------------------------------------------------


def test_query_log_ring_buffer_and_last_query():
    s = _session(max_query_log=3)
    assert s.last_query() is None
    # distinct query *texts* (same logical query: trailing spaces) so log
    # entries are tellable apart without five cold compiles
    queries = [Q + " " * n for n in (1, 2, 3, 4, 5)]
    for q in queries:
        s.sql(q)
    log = s.query_log
    assert len(log) == 3 and s.max_query_log == 3
    assert [e.query for e in log] == queries[-3:]   # oldest evicted, order kept
    last = s.last_query()
    assert last is log[-1] and last.query == queries[-1]
    assert last.source == "sql" and last.elapsed_s >= 0.0


def test_query_log_cap_validation():
    with pytest.raises(EngineError):
        _session(max_query_log=0)


# ---------------------------------------------------------------------------
# spans on the profiler's clock; the chunk time split; merge and densify
# ---------------------------------------------------------------------------


class _FakeTraceMe:
    """Stands in for jax's TraceAnnotation: records what opens and closes."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))
        return False


@pytest.fixture
def fake_traceme(monkeypatch):
    import jax

    _FakeTraceMe.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeTraceMe)
    return _FakeTraceMe.log


def test_null_tracer_emits_no_traceme(fake_traceme):
    s = _session()
    assert s.tracer is NULL_TRACER
    s.sql(Q)
    with NULL_TRACER.span("x"):
        NULL_TRACER.end(NULL_TRACER.start("y"))
    assert fake_traceme == []


def test_each_span_opens_and_closes_a_traceme(fake_traceme):
    s = _session(trace=True)
    s.sql(Q)
    spans = s.take_trace().spans
    enters = [n for kind, n, _ in fake_traceme if kind == "enter"]
    exits = [n for kind, n, _ in fake_traceme if kind == "exit"]
    assert sorted(enters) == sorted(exits) == sorted("repro." + sp.name for sp in spans)
    assert {"repro.query", "repro.execute", "repro.dispatch", "repro.merge",
            "repro.densify"} <= set(enters)
    assert all(sp.traceme is None for sp in spans)


def test_start_end_pair_across_threads_closes_its_traceme(fake_traceme):
    tr = Tracer()
    sp = tr.start("chunk")
    t = threading.Thread(target=tr.end, args=(sp,))
    t.start()
    t.join()
    (enter, exit_) = fake_traceme
    assert enter[:2] == ("enter", "repro.chunk") and exit_[:2] == ("exit", "repro.chunk")
    assert enter[2] != exit_[2]
    assert [x.name for x in tr.drain()] == ["chunk"]


@pytest.mark.parametrize("path", ["serial", "pool"])
def test_chunk_host_and_ready_add_up_to_chunk_time(path):
    plan = _pool_plan(_db()) if path == "pool" else get_backend("partitioned").compile(
        sql_to_forelem(Q, SCHEMAS), _db(),
        PartitionedChoices(n_partitions=4, schedule="fixed", async_dispatch=False))
    plan.run()
    log = plan.dispatch_log
    assert len(log) > 1
    for d in log:
        assert d.host_ms > 0 and d.ready_ms >= 0
        assert d.host_ms + d.ready_ms == d.t_ms
        if path == "serial":
            assert d.ready_ms == 0.0


@pytest.mark.parametrize("server", [False, True])
def test_host_and_ready_counters_add_up_to_busy(server):
    """The session's local pool and the server's shared pool both count the
    split beside ``worker.busy_ms``."""
    from repro import QueryServer

    if server:
        srv = QueryServer(n_partitions=4)
        try:
            srv.register("t", **_cols())
            srv.submit(Q)
            c = srv.metrics.snapshot()["counters"]
        finally:
            srv.close()
    else:
        s = _session(async_dispatch=True)
        s.sql(Q)
        c = s.metrics()["counters"]
    assert c["worker.host_ms"] > 0
    assert c["worker.host_ms"] + c["worker.ready_ms"] == pytest.approx(c["worker.busy_ms"])


def test_partitioned_run_has_merge_and_densify_spans():
    s = _session(trace=True)
    scalar = "SELECT SUM(v) FROM t WHERE k < 10"
    s.sql(Q)
    s.sql(scalar)
    qt = QueryTrace(s.take_trace().spans)
    execs = qt.by_name("execute")
    assert len(execs) == 2
    for name in ("merge", "densify"):
        got = qt.by_name(name)
        # one of each per query for these plans: the GROUP BY merges its
        # partials and reads its keys out under one span each
        assert len(got) >= 2, name
        for sp in got:
            assert qt.find(sp.parent).name == "execute"
    assert sorted(s.sql(Q).rows) == sorted(_session().sql(Q).rows)
