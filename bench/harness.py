"""One run of one cell, from generated tables to the result line.

``run_cell`` does everything but look for the chip (``bench/run.py`` does
that first), so the tests can drive a whole run on the CPU at a tiny size.
The order is the contract's: set-up (generate, register, warm up), the
measured window, the device's peak memory, the trace reduction, freeing the
engine, and only then the reference and the comparison.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import compare as cmp
from bench import query as qry
from bench import reference, spec
from bench import traffic as tr
from bench.trace_reduce import WINDOW, QUERY_PREFIX, TraceSummary, reduce_file

STRAGGLER_WAIT_S = 60.0


@dataclass
class Outcome:
    req: tr.Request
    t0: float            # due time (open loop) or start (closed loop)
    t1: float            # result in hand
    lateness_s: float = 0.0
    results: Optional[Dict[str, Any]] = None
    error: Optional[str] = None


@dataclass
class LayerContext:
    """What a per-layer metric reader may read (``bench/metrics/<name>.py``)."""

    n_queries: int
    spans: List[Any] = field(default_factory=list)          # repro.obs spans of the window
    counters: Dict[str, float] = field(default_factory=dict)  # deltas over the window
    hists: Dict[str, Tuple[float, float]] = field(default_factory=dict)  # (count, sum) deltas
    device: Optional[TraceSummary] = None
    executed: List[tr.Request] = field(default_factory=list)
    tables: Dict[str, Dict[str, np.ndarray]] = field(default_factory=dict)
    peaks: Dict[str, float] = field(default_factory=dict)


def device_mesh(chips: int) -> Any:
    """A one-axis mesh over the first ``chips`` devices."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:chips]), ("data",))


class Engine:
    """The engine entry a traffic file names, with its tables registered.
    The option ``"mesh": true`` becomes a mesh over the cell's ``chips``."""

    def __init__(self, entry: Dict[str, Any], tables, traced: bool, chips: int = 1):
        from repro import MapReduceSpec, QueryServer, Session

        self._mr = MapReduceSpec
        opts = dict(entry.get("options", {}))
        if opts.pop("mesh", False):
            opts["mesh"] = device_mesh(chips)
        self.server = entry["kind"] == "server"
        if self.server:
            self.obj: Any = QueryServer(**opts)
        else:
            self.obj = Session(trace=traced, **opts)
        for name, cols in tables.items():
            self.obj.register(name, **cols)
        self._texts: Dict[str, Any] = {}

    def _query(self, t: Dict[str, Any]) -> Any:
        q = self._texts.get(t["name"])
        if q is None:
            if t.get("api", "sql") == "mapreduce":
                table, key, value, op = qry.mapreduce_args(t["query"])
                q = self._mr.count(table, key) if value is None else \
                    self._mr.aggregate(table, key, value, op)
            else:
                q = qry.to_sql(t["query"])
            self._texts[t["name"]] = q
        return q

    def call(self, req: tr.Request) -> Dict[str, Any]:
        q = self._query(req.template)
        params = req.params or None
        if self.server:
            return self.obj.submit(q, params, tenant=req.tenant).results
        if isinstance(q, self._mr):
            return self.obj.mapreduce(q, params).results
        return self.obj.sql(q, params).results

    @property
    def metrics(self):
        return self.obj.metrics if self.server else self.obj.metrics_registry

    def take_spans(self) -> List[Any]:
        return [] if self.server else self.obj.take_trace().spans

    def close(self) -> None:
        if self.server:
            self.obj.close()


def _annotate(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def closed_loop(engine: Engine, traffic, seed: int, seconds: float, traced: bool) -> List[Outcome]:
    out: List[Outcome] = []
    deadline = time.perf_counter() + seconds
    i = 0
    while time.perf_counter() < deadline:
        req = tr.closed_request(traffic, seed, i)
        t0 = time.perf_counter()
        o = Outcome(req, t0, t0)
        with _annotate(traced, QUERY_PREFIX + req.template["name"]):
            try:
                o.results = engine.call(req)
            except Exception as e:  # counted as failed; the run goes on
                o.error = f"{type(e).__name__}: {e}"
        o.t1 = time.perf_counter()
        out.append(o)
        i += 1
    return out


def open_loop(engine: Engine, schedule: List[tr.Request], submitters: int,
              traced: bool) -> List[Outcome]:
    """Sends each request at its due time whatever is still running, on a
    pool of ``submitters`` threads; waits for stragglers, then gives up on
    the rest (they count as failed)."""
    outcomes: List[Optional[Outcome]] = [None] * len(schedule)
    start = time.perf_counter()

    def one(req: tr.Request, due: float) -> None:
        o = Outcome(req, due, due, lateness_s=time.perf_counter() - due)
        with _annotate(traced, QUERY_PREFIX + req.template["name"]):
            try:
                o.results = engine.call(req)
            except Exception as e:  # counted as failed; the run goes on
                o.error = f"{type(e).__name__}: {e}"
        o.t1 = time.perf_counter()
        outcomes[req.idx] = o

    pool = ThreadPoolExecutor(max_workers=submitters, thread_name_prefix="bench-submit")
    futures = []
    try:
        for req in schedule:
            due = start + req.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            futures.append(pool.submit(one, req, due))
        window_end = start + (schedule[-1].due_s if schedule else 0.0)
        wait(futures, timeout=max(0.0, window_end + STRAGGLER_WAIT_S - time.perf_counter()))
    finally:
        pool.shutdown(wait=True, cancel_futures=True)
    return [o if o is not None else Outcome(r, start + r.due_s, float("inf"), error="never answered")
            for r, o in zip(schedule, outcomes)]


def _hist_sums(snapshot: Dict[str, Any]) -> Dict[str, Tuple[float, float]]:
    return {k: (h["count"], h["sum"]) for k, h in snapshot.get("histograms", {}).items()}


def _compile_counter() -> Callable[[], int]:
    import jax

    n = [0]
    lock = threading.Lock()

    def listener(event: str, duration: float, **kw: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with lock:
                n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return lambda: n[0]


def memory_peak_bytes(n_chips: int) -> int:
    import jax

    peaks = []
    for d in jax.devices()[:n_chips]:
        st = d.memory_stats()
        peaks.append(int(st.get("peak_bytes_in_use", 0)) if st else 0)
    return max(peaks) if peaks else 0


def _percentile(x: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(x), p))


def run_cell(bench: Dict[str, Any], cell_name: str, seed: int, seconds: float, trace: bool,
             *, t_process: float, device: Dict[str, Any], peaks: Optional[Dict[str, float]] = None,
             trace_dir: Optional[Path] = None, config_override: Optional[Dict[str, Any]] = None,
             log=print) -> Dict[str, Any]:
    """One run; returns the result line's object (``checks`` last)."""
    import jax

    cell = spec.workload(bench, cell_name)
    cfg = config_override or spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    n_compiles = _compile_counter()

    t = time.perf_counter()
    tables = spec.generator(cfg).generate(cfg, seed)
    t_gen = time.perf_counter() - t
    import repro  # noqa: F401  (its import is not registration)

    t = time.perf_counter()
    engine = Engine(traffic["entry"], tables, traced=trace, chips=cell["chips"])
    t_reg = time.perf_counter() - t
    t = time.perf_counter()
    for req in tr.warmup_requests(traffic):
        engine.call(req)
    t_warm = time.perf_counter() - t
    compiles_setup = n_compiles()

    open_kind = traffic["loop"]["kind"] == "open"
    schedule = tr.open_schedule(traffic, seed, seconds) if open_kind else []
    engine.take_spans()
    before = engine.metrics.snapshot()
    if trace:
        trace_dir = trace_dir or spec.ROOT / ".bench_trace" / cell_name
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host annotations stay; Python calls go
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    with _annotate(trace, WINDOW):
        if open_kind:
            outcomes = open_loop(engine, schedule, traffic["loop"]["submitters"], trace)
        else:
            outcomes = closed_loop(engine, traffic, seed, seconds, trace)
    t_end = max(o.t1 for o in outcomes if o.t1 != float("inf")) if outcomes else t_window
    if trace:
        jax.profiler.stop_trace()
    compiles_window = n_compiles() - compiles_setup
    after = engine.metrics.snapshot()
    spans = engine.take_spans()
    device = dict(device, memory_peak_bytes=memory_peak_bytes(device["chips_used"]))
    device.pop("chips_used")

    failed = [o for o in outcomes if o.error is not None]
    done = [o for o in outcomes if o.error is None]
    log(f"setup: gen_s={t_gen:.3f} register_s={t_reg:.3f} warmup_s={t_warm:.3f} "
        f"setup_s={setup_s:.3f} compiles_setup={compiles_setup} compiles_window={compiles_window}")
    if open_kind and outcomes:
        late = [o.lateness_s for o in outcomes]
        log(f"generator lateness: mean_ms={1e3 * float(np.mean(late)):.3f} "
            f"max_ms={1e3 * float(np.max(late)):.3f} requests={len(outcomes)}")
    for o in failed[:5]:
        log(f"failed request {o.req.idx} ({o.req.template['name']}): {o.error}")

    metrics: Dict[str, Dict[str, Any]] = {}
    out: Dict[str, Any] = {"correct": False, "attempted": len(outcomes), "failed": len(failed)}
    if not trace:
        values: Dict[str, float] = {"setup_s": setup_s}
        if open_kind:
            lat_ms = [1e3 * (o.t1 - o.t0) for o in done]
            if lat_ms:
                for p in (50, 90, 95, 99):
                    values[f"latency_p{p}_ms"] = _percentile(lat_ms, p)
                log("latency ms: " + " ".join(f"{k}={v:.3f}" for k, v in values.items()
                                              if k.startswith("latency")) + f" n={len(lat_ms)}")
                by_t: Dict[str, List[float]] = {}
                for o in done:
                    by_t.setdefault(o.req.template["name"], []).append(1e3 * (o.t1 - o.t0))
                for name, v in sorted(by_t.items()):
                    log(f"latency ms {name}: p50={_percentile(v, 50):.3f} "
                        f"p90={_percentile(v, 90):.3f} n={len(v)}")
        elif done:
            values["qps"] = len(outcomes) / (t_end - t_window)
        for m in spec.cell_metrics(bench, cell_name, "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        summary = None
        if trace_dir is not None:
            found = sorted(trace_dir.glob("**/*.xplane.pb"))
            summary = reduce_file(str(found[-1])) if found else None
            shutil.rmtree(trace_dir, ignore_errors=True)
        hb, ha = _hist_sums(before), _hist_sums(after)
        ctx = LayerContext(
            n_queries=len(done),
            spans=spans,
            counters={k: v - before["counters"].get(k, 0.0) for k, v in after["counters"].items()},
            hists={k: (c - hb.get(k, (0, 0))[0], s - hb.get(k, (0, 0))[1]) for k, (c, s) in ha.items()},
            device=summary,
            executed=[o.req for o in done],
            tables=tables,
            peaks=peaks or {},
        )
        for m in spec.cell_metrics(bench, cell_name, "per_layer"):
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device.update(busy_s=summary.busy_s, window_s=summary.window_s)
            out["breakdown"] = {"device_ops": summary.top_ops(), "idle_gaps": summary.top_gaps()}
    out["metrics"] = metrics
    out["device"] = device

    # the engine's state goes before the reference runs
    engine.close()
    del engine
    gc.collect()
    checks = check_answers(traffic, tables, done)
    n_compared = checks.pop("answers_compared")
    log(f"answers compared: {n_compared}")
    out["correct"] = (not failed and n_compared > 0
                      and all(c["value"] <= c["limit"] for c in checks.values()))
    out["checks"] = checks
    return out


def check_answers(traffic: Dict[str, Any], tables, done: List[Outcome]) -> Dict[str, Any]:
    """Every finished answer against the reference; returns each number
    beside its limit (an infinite gap as 1e308, to stay JSON), and how many
    answers were compared under ``answers_compared``."""
    chk = traffic["check"]
    refs: Dict[Any, reference.Answer] = {}
    readings = []
    for o in done:
        t = o.req.template
        key = (t["name"], tuple(sorted((k, float(v)) for k, v in o.req.params.items())))
        if key not in refs:
            refs[key] = reference.evaluate(t["query"], tables, o.req.params)
        got = cmp.engine_answer(t["query"], o.results)
        readings.append(cmp.compare(t["query"], got, refs[key]))
    worst = cmp.worst(readings) or {"rel_err": 0.0, "bad_keys": 0.0}
    return {
        "answers_compared": len(readings),
        "bad_keys": {"value": worst["bad_keys"], "limit": float(chk.get("bad_keys_limit", 0))},
        "rel_err": {"value": min(worst["rel_err"], 1e308), "limit": float(chk["rel_err_limit"])},
    }


def print_result(out: Dict[str, Any]) -> None:
    """Compared numbers as the last lines of stderr; the result as the last
    line of stdout."""
    import json

    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
