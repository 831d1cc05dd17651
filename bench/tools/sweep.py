"""Finds the highest rate a served cell sustains: one process generates the
cell's tables, warms up, then runs its open loop at each rate in turn.

    python bench/tools/sweep.py --workload tpch.mix.serve --seed 5 --seconds 40 \
        --rates 0.5,1,1.5,2

Prints one JSON line per rate: completions, latency percentiles, how late
the generator ran, and the backlog trend (median latency of the last third
of requests over that of the first third; near 1 where the rate is
sustained, growing with the window where it is not).  Stops after the
first rate whose trend passes 2.  The chosen rate is written by hand into
the traffic file (``loop.rate_qps``).
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()

    import numpy as np

    from bench import spec
    from bench import traffic as tr
    from bench.harness import Engine, open_loop
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    tables = spec.generator(cfg).generate(cfg, args.seed)
    engine = Engine(traffic["entry"], tables, traced=False, chips=cell["chips"])
    for req in tr.warmup_requests(traffic):
        engine.call(req)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            sched = tr.open_schedule(traffic, args.seed, args.seconds, rate_qps=rate)
            t0 = time.perf_counter()
            outs = open_loop(engine, sched, traffic["loop"]["submitters"], traced=False)
            done = [o for o in outs if o.error is None]
            lat = np.array([1e3 * (o.t1 - o.t0) for o in done])
            third = max(1, len(done) // 3)
            trend = float(np.median(lat[-third:]) / np.median(lat[:third])) if len(done) >= 3 else None
            by_t = {}
            for o in done:
                by_t.setdefault(o.req.template["name"], []).append(1e3 * (o.t1 - o.t0))
            print(json.dumps({
                "rate_qps": rate, "requests": len(outs), "failed": len(outs) - len(done),
                "p50_ms": float(np.percentile(lat, 50)), "p90_ms": float(np.percentile(lat, 90)),
                "p95_ms": float(np.percentile(lat, 95)),
                "median_ms_by_template": {k: float(np.median(v)) for k, v in by_t.items()},
                "late_max_ms": 1e3 * max(o.lateness_s for o in outs),
                "drained_s": time.perf_counter() - t0, "backlog_trend": trend,
            }), flush=True)
            if trend is not None and trend > 2:
                break
    finally:
        engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
