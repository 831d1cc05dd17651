"""The comparison and its control fail where they must.

A run with an answer altered where the engine produces it comes out not
correct, in every cell; so does the control (the reference computed in
bfloat16 in the engine's place), against each cell's limit."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import control, spec  # noqa: E402
from bench.tests._tiny import CELLS, run_tiny, tiny_config  # noqa: E402

BENCH = spec.load_benchmark(ROOT)


def _altered(real):
    def apply_order_limit(program, out):
        out = real(program, out)
        if out.get("R"):
            row = list(out["R"][0])
            row[-1] = row[-1] * 1.001
            out["R"][0] = tuple(row)
        elif "scalar" in out:
            out["scalar"] = out["scalar"] * 1.001
        return out
    return apply_order_limit


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_not_correct(cell, monkeypatch):
    import repro.backends.jax_vec as jv
    import repro.backends.partitioned as pt

    monkeypatch.setenv("REPRO_PALLAS", "1")
    monkeypatch.setattr(jv, "apply_order_limit", _altered(jv.apply_order_limit))
    monkeypatch.setattr(pt, "apply_order_limit", _altered(pt.apply_order_limit))
    out = run_tiny(BENCH, cell, 3)
    assert not out["correct"]
    assert out["checks"]["rel_err"]["value"] > out["checks"]["rel_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    traffic = spec.traffic(spec.workload(BENCH, cell)["traffic"])
    r = control.run(BENCH, cell, 2**31 + 11, 20.0, cfg=tiny_config(BENCH, cell))
    assert r["rel_err"] > traffic["check"]["rel_err_limit"]
