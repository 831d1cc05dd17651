"""Reads the control (``bench/control.py``) of each named cell at its own
size, on several seeds, one JSON line per reading.

    python bench/tools/control.py --workloads bdb.agg_small.batch,tpch.mix.serve \
        --seeds 11,12,13 --seconds 51
"""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from bench import control, spec

    bench = spec.load_benchmark(ROOT)
    for cell in args.workloads.split(","):
        limit = spec.traffic(spec.workload(bench, cell)["traffic"])["check"]["rel_err_limit"]
        for seed in (int(s) for s in args.seeds.split(",")):
            r = control.run(bench, cell, seed, args.seconds)
            print(json.dumps({"workload": cell, "seed": seed, "control": r,
                              "rel_err_limit": limit}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
