"""Breaks one cell's time down by engine stage and device program, and times
its untraced window with the engine's tracer on against off.

    python bench/tools/stages.py --workload tpch.mix.serve --seed 7 --seconds 51 \
        --pairs 3 --pair-seconds 51

One process, on the chip.  The cell's tables are made from ``--seed`` and
registered in two engines, one with ``repro.obs`` tracing off and one with
it on, and both are warmed up.  Then ``--pairs`` pairs of untraced windows
run, off then on, or on then off, in turn; pair ``i`` draws its requests
from seed ``--seed + 1 + i``.  Last, one window of ``--seconds`` runs on the
tracing engine under ``jax.profiler``.  Its trace is reduced by
``bench/stage_reduce.py`` and printed with the program's readings per
query: the cell's per-layer metrics, the ``merge`` and ``densify`` spans,
the chunks' host, ready and device time.  One JSON line per window.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def make_engine(entry: Dict[str, Any], tables, chips: int, traced: bool):
    from bench.harness import Engine

    if entry["kind"] == "server":
        entry = dict(entry, options=dict(entry.get("options", {}), trace=traced))
    return Engine(entry, tables, traced=traced, chips=chips)


def window(engine, traffic, seed: int, seconds: float, traced: bool) -> Dict[str, Any]:
    """One window of the cell's loop; its end-to-end metrics as the harness
    computes them."""
    import numpy as np

    from bench import harness
    from bench import traffic as tr

    open_kind = traffic["loop"]["kind"] == "open"
    t0 = time.perf_counter()
    if open_kind:
        outs = harness.open_loop(engine, tr.open_schedule(traffic, seed, seconds),
                                 traffic["loop"]["submitters"], traced)
    else:
        outs = harness.closed_loop(engine, traffic, seed, seconds, traced)
    done = [o for o in outs if o.error is None]
    rec: Dict[str, Any] = {"seed": seed, "requests": len(outs), "failed": len(outs) - len(done)}
    if open_kind and done:
        lat = [1e3 * (o.t1 - o.t0) for o in done]
        rec.update(latency_p50_ms=float(np.percentile(lat, 50)),
                   latency_p90_ms=float(np.percentile(lat, 90)))
    elif done:
        rec["qps"] = len(outs) / (max(o.t1 for o in outs if o.t1 != float("inf")) - t0)
    rec["_done"] = done
    return rec


def breakdown(bench, cell_name: str, engine, traffic, seed: int, seconds: float, tables,
              peaks, trace_dir: Path) -> Dict[str, Any]:
    """A profiled window on the tracing engine, reduced by stage."""
    import jax

    from bench import spec, stage_reduce
    from bench.harness import WINDOW, LayerContext, _annotate
    from bench.layer_read import counter_per_query, span_ms_per_query

    engine.obj.tracer.drain()
    before = engine.metrics.snapshot()["counters"]
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with _annotate(True, WINDOW):
        rec = window(engine, traffic, seed, seconds, traced=True)
    jax.profiler.stop_trace()
    after = engine.metrics.snapshot()["counters"]
    found = sorted(trace_dir.glob("**/*.xplane.pb"))
    st = stage_reduce.reduce_file(str(found[-1])) if found else None
    shutil.rmtree(trace_dir, ignore_errors=True)
    done = rec.pop("_done")
    ctx = LayerContext(
        n_queries=len(done), spans=engine.obj.tracer.drain(),
        counters={k: v - before.get(k, 0.0) for k, v in after.items()},
        device=st.base if st is not None else None,
        executed=[o.req for o in done], tables=tables, peaks=peaks or {},
    )
    layers = {m["name"]: spec.metric_reader(m["name"])(ctx)
              for m in spec.cell_metrics(bench, cell_name, "per_layer")}
    n = max(1, len(done))
    program: Dict[str, Optional[float]] = {
        name: span_ms_per_query(ctx, (name,))
        for name in ("jax.upload", "jax.compute", "merge", "densify", "dispatch")
    }
    for c in ("worker.busy_ms", "worker.host_ms", "worker.ready_ms", "queue.wait_ms"):
        program[c] = counter_per_query(ctx, c)
    rec.update(per_layer=layers, program=program)
    if st is not None:
        chunk_s = sum(v for k, v in st.module_s.items() if k.startswith("chunk_"))
        program["chunk_device_ms"] = 1e3 * chunk_s / n if chunk_s else None
        rec.update(
            busy_s=st.base.busy_s, window_s=st.base.window_s,
            in_query_idle_s=st.in_query_idle_s(), named_idle_s=st.named_idle_s(),
            idle_gaps=st.top_gaps(16), device_ops=st.top_ops(12),
            module_s=sorted(st.module_s.items(), key=lambda kv: -kv[1])[:12],
            query_gaps=st.base.top_gaps(),
        )
    return rec


def run(bench, cell_name: str, seed: int, seconds: float, pairs: int, pair_seconds: float, *,
        cfg: Optional[Dict[str, Any]] = None, peaks=None, trace_dir: Optional[Path] = None,
        emit: Callable[[Dict[str, Any]], None] = print) -> None:
    import gc

    from bench import spec
    from bench import traffic as tr
    from bench.harness import _compile_counter

    cell = spec.workload(bench, cell_name)
    cfg = cfg or spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    n_compiles = _compile_counter()
    tables = spec.generator(cfg).generate(cfg, seed)
    engines = {on: make_engine(traffic["entry"], tables, cell["chips"], on) for on in (False, True)}
    try:
        for e in engines.values():
            for req in tr.warmup_requests(traffic):
                e.call(req)
            e.obj.tracer.drain()
        emit({"workload": cell_name, "phase": "setup", "compiles": n_compiles()})
        for i in range(pairs):
            for on in ((False, True) if i % 2 == 0 else (True, False)):
                c0 = n_compiles()
                rec = window(engines[on], traffic, seed + 1 + i, pair_seconds, traced=False)
                rec.pop("_done")
                spans = len(engines[on].obj.tracer.drain())
                emit(dict(rec, workload=cell_name, phase="pair", pair=i,
                          tracer="on" if on else "off", spans=spans,
                          compiles=n_compiles() - c0))
        c0 = n_compiles()
        rec = breakdown(bench, cell_name, engines[True], traffic, seed + 1 + pairs, seconds,
                        tables, peaks, trace_dir or spec.ROOT / ".bench_trace" / "stages")
        emit(dict(rec, workload=cell_name, phase="traced", compiles=n_compiles() - c0))
    finally:
        for e in engines.values():
            e.close()
        engines.clear()
        gc.collect()


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=3)
    ap.add_argument("--pair-seconds", type=float, default=51.0)
    args = ap.parse_args(argv)

    import jax

    from bench import spec
    from bench.peaks import peaks_for
    from repro.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}", file=sys.stderr)
        return 2
    use_compile_cache()
    run(spec.load_benchmark(ROOT), args.workload, args.seed, args.seconds, args.pairs,
        args.pair_seconds, peaks=peaks_for(dev.device_kind),
        emit=lambda r: print(json.dumps(r), flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
