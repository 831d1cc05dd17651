# The monolithic shard_map path on a four-device mesh: a GROUP BY's table is
# placed on the mesh as row shards (padded to a multiple of the mesh size,
# the padding masked out), each device aggregates its own rows, and one
# collective per accumulator combines them.  Four host CPU devices need a
# process of their own (XLA_FLAGS is read when JAX starts), so one child
# process runs every case and the tests below read its report.
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# ROWS is not a multiple of 4, so the last shard carries padding.  MIN/MAX
# values are all negative: a padded row that reached key 0 with value 0
# would change that key's MAX.
CHILD = r"""
import json, os, re, sys
sys.path[:0] = ["src"]
import numpy as np, jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro import OptimizeOptions, Session, optimize, sql_to_forelem
from repro.backends import ReferenceInterpreter

ROWS = (1 << 18) + 3
SCHEMA = {"t": ["k", "v", "f"]}
mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
rng = np.random.default_rng(2**31 + 17)
cols = {"k": rng.integers(0, 37, ROWS).astype(np.int32),
        "v": rng.integers(-80, -20, ROWS).astype(np.int32),
        "f": rng.random(ROWS).astype(np.float32)}

def norm(rows):
    return sorted(tuple(float(x) for x in r) for r in rows)

def collectives(text):
    # operands of each all-reduce (XLA may combine several into one tuple)
    ar = sum(len(re.findall(r"%[\w.-]+", m)) for m in re.findall(r"all-reduce(?:-start)?\(([^)]*)\)", text))
    moves = len(re.findall(r"(all-gather|all-to-all|collective-permute|reduce-scatter)(-start)?\(", text))
    return ar, moves

def report(plan, rows, db, sql):
    ref = ReferenceInterpreter(db).run(sql_to_forelem(sql, SCHEMA))["R"]
    placed = plan.input_columns()
    text = plan.fn.lower(placed).compile().as_text()
    ar, moves = collectives(text)
    return {
        "correct": norm(rows) == norm(ref),
        "mesh_rows": plan.lowering.mesh_rows,
        "collectives": plan.lowering.collectives,
        "allreduce_operands": ar,
        "data_movement": moves,
        "shardings": {f: {"mesh": c.sharding == NamedSharding(mesh, P("data")),
                          "shards": sorted(s.data.shape[0] for s in c.addressable_shards),
                          "devices": len(c.sharding.device_set)}
                      for f, c in placed.get("t", {}).items()},
        "upload_bytes": plan.upload_bytes,
        "resident_bytes": plan.resident_bytes,
        "segreduce_path": plan.lowering.segreduce_path,
    }

QUERIES = {
    "sum": "SELECT k, SUM(v) FROM t GROUP BY k",
    "count": "SELECT k, COUNT(v) FROM t GROUP BY k",
    "min": "SELECT k, MIN(v) FROM t GROUP BY k",
    "max": "SELECT k, MAX(v) FROM t GROUP BY k",
    "filtered": "SELECT k, SUM(v) FROM t WHERE f > 0.5 GROUP BY k",
}
out = {}
for name, sql in QUERIES.items():
    s = Session(mesh=mesh, n_parts=4, revalidate="signature")
    s.register("t", **cols)
    runs = []
    for _ in range(2):  # the second run of the same plan reads its columns resident
        r = s.sql(sql)
        runs.append([r.plan.upload_bytes, r.plan.resident_bytes])
    out[name] = report(r.plan, r.rows, s.db, sql)
    out[name]["runs"] = runs
    out[name]["parallel"] = r.decision.chosen.parallel
    out[name]["counters"] = s.metrics_registry.snapshot()["counters"]

# the segreduce kernel on each device (the chip's choice), in interpret mode
os.environ["REPRO_PALLAS"] = "1"
for name in ("sum", "max"):
    sql = QUERIES[name]
    res = optimize(sql_to_forelem(sql, SCHEMA), s.db, OptimizeOptions(
        n_parts=4, agg_method="kernel", parallel_exec="shard_map", mesh=mesh))
    rows = res.plan.run()["R"]
    out["kernel_" + name] = report(res.plan, rows, s.db, sql)
    out["kernel_" + name]["runs"] = [[res.plan.upload_bytes, res.plan.resident_bytes]]

# ... and over 3,000 keys, where each device contracts the sum on the MXU
w = Session(mesh=mesh, n_parts=4, revalidate="signature")
w.register("t", **dict(cols, k=rng.integers(0, 3000, ROWS).astype(np.int32)))
sql = QUERIES["sum"]
res = optimize(sql_to_forelem(sql, SCHEMA), w.db, OptimizeOptions(
    n_parts=4, agg_method="kernel", parallel_exec="shard_map", mesh=mesh))
rows = res.plan.run()["R"]
out["kernel_sum_mxu"] = report(res.plan, rows, w.db, sql)
out["kernel_sum_mxu"]["runs"] = [[res.plan.upload_bytes, res.plan.resident_bytes]]

# a row count the mesh divides: no padding
s = Session(mesh=mesh, n_parts=4, revalidate="signature")
s.register("t", **{c: a[: 1 << 18] for c, a in cols.items()})
runs = []
for _ in range(2):
    r = s.sql(QUERIES["sum"])
    runs.append([r.plan.upload_bytes, r.plan.resident_bytes])
out["divisible"] = report(r.plan, r.rows, s.db, QUERIES["sum"])
out["divisible"]["runs"] = runs
print(json.dumps(out))
"""

CASES = ("sum", "count", "min", "max", "filtered", "kernel_sum", "kernel_max", "divisible",
         "kernel_sum_mxu")
IN_SESSION = ("sum", "count", "min", "max", "filtered", "divisible")
ON_MESH = ("sum", "count", "min", "max", "kernel_sum", "kernel_max", "divisible",
           "kernel_sum_mxu")


@pytest.fixture(scope="module")
def mesh_report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
def test_mesh_answers_equal_reference(mesh_report, case):
    assert mesh_report[case]["correct"], mesh_report[case]


@pytest.mark.parametrize("case", ON_MESH)
def test_columns_placed_as_row_shards(mesh_report, case):
    r = mesh_report[case]
    rows = (1 << 18) if case == "divisible" else (1 << 18) + 3
    assert r["mesh_rows"] == {"t": rows}
    per = -(-rows // 4)
    assert r["shardings"] and all(
        s == {"mesh": True, "shards": [per] * 4, "devices": 4} for s in r["shardings"].values()
    ), r["shardings"]
    want = {"sharded": 4 * per * 4 * len(r["shardings"])}
    if case in IN_SESSION:
        # the first run places the columns, later lookups read them resident
        assert r["runs"] == [[want, {}], [{}, want]]
        assert (r["upload_bytes"], r["resident_bytes"]) == ({}, want)
    else:
        # a plan run outside a session places them again on every run
        assert r["runs"] == [[want, {}]]
        assert (r["upload_bytes"], r["resident_bytes"]) == (want, {})


def test_session_plans_shard_map(mesh_report):
    assert all(mesh_report[c]["parallel"] == "shard_map" for c in ("sum", "count", "min", "max"))
    # the session's indirect partitioning leaves a filtered loop whole: its
    # table stays on one device, and the program combines nothing
    f = mesh_report["filtered"]
    assert f["mesh_rows"] == {} and f["collectives"] == [] and f["allreduce_operands"] == 0
    want = {"single": 3 * ((1 << 18) + 3) * 4}
    assert f["runs"] == [[want, {}], [{}, want]]
    assert (f["upload_bytes"], f["resident_bytes"]) == ({}, want)


@pytest.mark.parametrize("case", ON_MESH)
def test_one_combine_per_accumulator(mesh_report, case):
    r = mesh_report[case]
    op = {"min": "pmin", "max": "pmax", "kernel_max": "pmax"}.get(case, "psum")
    assert r["collectives"] == [op, "psum"]
    assert r["data_movement"] == 0
    # COUNT's value and presence are the same array, which XLA reduces once
    assert r["allreduce_operands"] == (1 if case == "count" else 2)


@pytest.mark.parametrize(
    "case,path", [("sum", []), ("kernel_sum", ["vpu", "vpu"]), ("kernel_max", ["vpu", "vpu"]),
                  ("kernel_sum_mxu", ["mxu", "mxu"])]
)
def test_kernel_path_of_each_launch_on_the_mesh(mesh_report, case, path):
    # the aggregate's launch and the presence count's, on every device
    assert mesh_report[case]["segreduce_path"] == path


@pytest.mark.parametrize("case", ("sum", "max"))
def test_counters_read_what_the_runs_placed_and_combined(mesh_report, case):
    r = mesh_report[case]
    c = r["counters"]
    # two runs: the first placed the columns, the second read them resident
    placed = r["runs"][0][0]["sharded"]
    assert c["upload.bytes{placement=sharded}"] == placed
    assert c["resident.bytes{placement=sharded}"] == placed
    assert "upload.bytes{placement=single}" not in c
    assert "resident.bytes{placement=single}" not in c
    n_cols = len(r["shardings"])
    assert c["columns.resident{result=miss}"] == n_cols
    assert c["columns.resident{result=hit}"] == n_cols
    assert c["mesh.collectives{op=psum}"] == 2 * r["collectives"].count("psum")
    assert c.get("mesh.collectives{op=pmax}", 0) == 2 * r["collectives"].count("pmax")
