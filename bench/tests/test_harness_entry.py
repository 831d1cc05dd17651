"""``bench/run.py`` refuses to run, and prints no result, without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_run_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS="1")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bdb.agg_small.batch",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
