"""The control of the comparison that decides ``correct``: the reference
computed in bfloat16 (every float column stored in bfloat16, the nearest
precision below the configurations' float32) put in the engine's place.
It has to come out as not correct: its ``rel_err`` must lie above the
cell's limit.  ``bench/tools/control.py`` reads it at a cell's own size on
the chip's host; ``bench/tests`` at a tiny size.
"""
from __future__ import annotations

from typing import Any, Dict, List

from bench import compare as cmp
from bench import reference, spec
from bench import traffic as tr


def compared_requests(traffic: Dict[str, Any], seed: int, seconds: float,
                      n_closed: int = 8) -> List[tr.Request]:
    """The requests a run compares: the open loop's whole schedule, drawn as
    a run draws it, or the first ``n_closed`` of a closed loop."""
    if traffic["loop"]["kind"] == "open":
        return tr.open_schedule(traffic, seed, seconds)
    return [tr.closed_request(traffic, seed, i) for i in range(n_closed)]


def control_readings(traffic: Dict[str, Any], tables, requests: List[tr.Request]) -> Dict[str, float]:
    readings = []
    for req in requests:
        q = req.template["query"]
        want = reference.evaluate(q, tables, req.params)
        got = reference.returned_rows(q, reference.evaluate(q, tables, req.params, "bfloat16"))
        readings.append(cmp.compare(q, got, want))
    return cmp.worst(readings) or {}


def run(bench: Dict[str, Any], cell_name: str, seed: int, seconds: float,
        cfg: Dict[str, Any] = None) -> Dict[str, float]:
    cell = spec.workload(bench, cell_name)
    cfg = cfg or spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    tables = spec.generator(cfg).generate(cfg, seed)
    return control_readings(traffic, tables, compared_requests(traffic, seed, seconds))
