"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in files of its
own, so a later change adds a cell by adding files and entries:

- ``configs[].file``: the configuration as it is run (sizes, source, cuts,
  guarantees).  Its ``generator`` key names ``bench/configs/<generator>.py``,
  which makes the tables from the seed.
- ``bench/traffic/<traffic>.json``: the query templates, parameter draws,
  loop kind, tenants, rate and the correctness limits.
- ``bench/metrics/<metric>.py``: one reader per per-layer metric.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _by_name(bench["workloads"], name, "workload")


def config(bench: Dict[str, Any], name: str, root: Path = ROOT) -> Dict[str, Any]:
    entry = _by_name(bench["configs"], name, "configuration")
    with open(root / entry["file"]) as f:
        return json.load(f)


def traffic(name: str) -> Dict[str, Any]:
    with open(BENCH_DIR / "traffic" / f"{name}.json") as f:
        return json.load(f)


def _load_module(path: Path, modname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(cfg: Dict[str, Any]) -> ModuleType:
    """The module that makes a configuration's tables: ``generate(cfg, seed)``."""
    name = cfg["generator"]
    return _load_module(BENCH_DIR / "configs" / f"{name}.py", f"bench_config_{name}")


def metric_reader(name: str) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    mod = _load_module(BENCH_DIR / "metrics" / f"{name}.py", f"bench_metric_{name.replace('.', '_')}")
    return mod.read


def cell_metrics(bench: Dict[str, Any], cell: str, kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports: those
    whose ``workloads`` list names it, or that have no such list."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]
