"""A cell on four chips needs only data: a traffic entry with ``"mesh": true``
runs its session over a mesh of the cell's chips.  Driven in a child process
with four host devices, at a tiny size, past the harness's look for a chip."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys, time
sys.path[:0] = [".", "src"]
import jax
from repro import Session
from bench import spec
from bench.tests._tiny import CPU_DEVICE, V5E, tiny_config
from bench.harness import run_cell

chosen = []
for name in ("sql", "mapreduce"):
    real = getattr(Session, name)
    def wrapped(self, *a, _real=real, **k):
        r = _real(self, *a, **k)
        chosen.append((len(self.mesh.devices.flat) if self.mesh is not None else 0,
                       r.decision.chosen.parallel if r.decision else None))
        return r
    setattr(Session, name, wrapped)

bench = spec.load_benchmark()
cell = dict(spec.workload(bench, "bdb.agg_small.batch"), name="mesh_cell", chips=4)
bench["workloads"].append(cell)
real_traffic = spec.traffic
def traffic(name):
    t = real_traffic(name)
    t["entry"]["options"].update(mesh=True, n_parts=4)
    return t
spec.traffic = traffic
cfg = tiny_config(bench, "bdb.agg_small.batch")
cfg["uservisits_rows"] = 1 << 18
out = run_cell(bench, "mesh_cell", 2**31 + 3, 1.0, False, t_process=time.perf_counter(),
               device=dict(CPU_DEVICE, count=4, chips_used=4), peaks=V5E,
               config_override=cfg, log=lambda s: None)
print(json.dumps({"correct": out["correct"], "attempted": out["attempted"],
                  "devices": len(jax.devices()), "chosen": sorted(set(chosen))}))
"""


def test_mesh_entry_runs_on_the_cells_chips():
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_PALLAS="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.splitlines()[-1])
    assert r["devices"] == 4 and r["correct"] and r["attempted"] > 0
    assert [4, "shard_map"] in r["chosen"], r
