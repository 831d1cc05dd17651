"""Runs one benchmark cell on the chip and prints its result line.

    python bench/run.py --workload bdb.agg_small.batch --seed 7 --seconds 30 --trace 0

Run from the root of a checkout.  Each run is one process: it turns on the
compile cache, makes the configuration's tables from ``--seed``, registers
them, warms up the cell's query shapes, measures for ``--seconds``, and
prints one JSON object as the last line of stdout.  ``--trace 1`` reports
the cell's per-layer metrics from a profiler trace and the engine's spans
and counters instead of its end-to-end metrics.  It exits non-zero, with no
result, where JAX finds no TPU, fewer chips than the cell asks for, or a
segreduce kernel that would not run compiled.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec

    bench = spec.load_benchmark(ROOT)
    cell = spec.workload(bench, args.workload)

    import jax

    from repro.compile_cache import use_compile_cache
    from repro.kernels.segreduce.ops import pallas_mode

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    if pallas_mode() != "compiled":
        print(f"pallas_mode() is {pallas_mode()!r}, not 'compiled'", file=sys.stderr)
        return 2
    from bench.harness import print_result, run_cell
    from bench.peaks import peaks_for

    peaks = peaks_for(dev.device_kind)
    cache_dir = use_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    out = run_cell(
        bench, args.workload, args.seed, args.seconds, bool(args.trace),
        t_process=T_PROCESS, peaks=peaks,
        device={"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
                "chips_used": cell["chips"]},
        log=lambda s: print(s, flush=True),
    )
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
