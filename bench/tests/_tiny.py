"""Tiny versions of the benchmark's configurations, for CPU tests: the same
generators, schema and traffic at a few hundred thousand rows at most."""
from __future__ import annotations

import copy
import time
from typing import Any, Dict

from bench import spec

CELLS = ("bdb.agg_small.batch", "bdb.join.batch", "tpch.mix.serve")
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1, "chips_used": 1}
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def tiny_config(bench: Dict[str, Any], cell: str) -> Dict[str, Any]:
    cfg = copy.deepcopy(spec.config(bench, spec.workload(bench, cell)["config"]))
    if "uservisits_rows" in cfg:
        cfg["uservisits_rows"], cfg["rankings_rows"] = 1 << 16, 1 << 13
    else:
        cfg["orders"] = 14000
    return cfg


def run_tiny(bench: Dict[str, Any], cell: str, seed: int, seconds: float = 1.5,
             trace: bool = False, **kw) -> Dict[str, Any]:
    from bench.harness import run_cell

    return run_cell(bench, cell, seed, seconds, trace, t_process=time.perf_counter(),
                    device=dict(CPU_DEVICE), peaks=V5E, config_override=tiny_config(bench, cell),
                    log=lambda s: None, **kw)
