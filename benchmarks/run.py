# Benchmark harness: one module per paper table/figure + substrate benches.
# Prints ``name,us_per_call,derived`` CSV (and tees a copy under runs/).
# Exits non-zero when any suite fails — CI must not mistake a partial
# report set for a complete run.
#
# ``--ci`` runs only the CI-gated smoke suites (the ones whose BENCH_*.json
# reports check_regression.py compares against committed baselines) — the
# single benchmark step both ci.yml and nightly.yml share.
from __future__ import annotations

import argparse
import os
import sys
import traceback

# suites whose reports the CI regression gate consumes
CI_SUITES = ("kernels", "planner", "join", "engine", "partition", "serve", "trace", "adaptive")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ci", action="store_true",
                    help="run only the CI-gated smoke suites (skip the "
                         "paper-figure measurement suites)")
    args = ap.parse_args(argv)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    rows = []
    failed = []
    from . import (
        bench_adaptive,
        bench_engine,
        bench_fig2,
        bench_join,
        bench_kernels,
        bench_partition,
        bench_planner,
        bench_sched,
        bench_serve,
        bench_trace,
    )

    suites = [
        ("fig2", bench_fig2.run),
        ("kernels", bench_kernels.run),
        ("sched", bench_sched.run),
        ("planner", bench_planner.run),
        ("join", bench_join.run),
        ("engine", bench_engine.run),
        ("partition", bench_partition.run),
        ("serve", bench_serve.run),   # writes BENCH_serve.json (QPS/p99 gate)
        ("trace", bench_trace.run),   # writes BENCH_trace.json.gz (CI artifact)
        ("adaptive", bench_adaptive.run),  # writes BENCH_adaptive.json (replan gate)
    ]
    if args.ci:
        suites = [s for s in suites if s[0] in CI_SUITES]
    print("name,us_per_call,derived")
    for name, fn in suites:
        try:
            for row in fn():
                rows.append(row)
                print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
        except Exception as e:
            failed.append(name)
            print(f"{name}_FAILED,0,{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    os.makedirs("runs", exist_ok=True)
    with open("runs/bench_latest.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        for r in rows:
            f.write(f"{r[0]},{r[1]:.1f},{r[2]}\n")
    if failed:
        print(f"benchmark suite(s) failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
