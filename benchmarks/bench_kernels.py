# Kernel micro-benchmarks.  On this CPU container the *jnp reference paths*
# are timed (wall-clock of Pallas interpret mode measures the Python
# interpreter, not the kernel); the Pallas kernel itself is validated for
# correctness in tests/ and timed on the chip by chip_segreduce.py.
# derived = achieved GB/s of the jnp path on CPU.
#
# The fused-segreduce section benchmarks the PR's claim directly: the fused
# multi-aggregate path (one data pass, aggregates stacked per op/dtype
# family) vs the unfused per-aggregate path (one funnel + one scatter per
# aggregate, plus a presence pass) at BENCH_N_ROWS rows x {1, 2, 4}
# aggregates, timed round-robin.  ``key_ratios`` holds the fused-over-
# unfused speedups (higher-is-better, gated by check_regression.py);
# ``key_counts`` holds the partitioned backend's chunk-kernel jit compile
# counts for a 4-aggregate GROUP BY under agg_method='kernel' (one fused
# chunk kernel) vs 'dense' (one kernel per aggregate) — lower-is-better,
# so a regression that decomposes the fused unit back into per-aggregate
# kernels fails CI even when small-scale wall-clock hides it.
#
# Emits BENCH_kernels.json.  Run:  PYTHONPATH=src python benchmarks/bench_kernels.py
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp

N_ROWS = int(os.environ.get("BENCH_N_ROWS", 1_500_000))
N_KEYS = 4_096
AGG_COUNTS = (1, 2, 4)


def _timeit(fn, repeats: int = 5) -> float:
    fn()  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _best_interleaved(variants: Dict[str, object], repeats: int = 5) -> Dict[str, float]:
    """Best-of-N per variant, timed round-robin in each round so machine-
    speed drift (shared runners) biases every variant equally."""
    for fn in variants.values():
        fn()  # compile
    best = {name: float("inf") for name in variants}
    for _ in range(repeats):
        for name, fn in variants.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[name] = min(best[name], time.perf_counter() - t0)
    return best


def _fused_vs_unfused(rng, report: Dict, out: List[Tuple[str, float, str]]) -> None:
    """The tentpole claim: one fused multi-aggregate pass vs N per-aggregate
    passes over the same filtered GROUP BY, on the path CI actually runs
    (the jnp fused fallback — REPRO_PALLAS resolves 'off' on CPU)."""
    from repro.kernels.segreduce import ops as segops
    from repro.kernels.segreduce.kernel import op_identity

    keys = jnp.asarray(rng.integers(0, N_KEYS, N_ROWS), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, N_ROWS), jnp.int32)
    cols = [jnp.asarray(rng.normal(size=N_ROWS), jnp.float32) for _ in range(max(AGG_COUNTS))]

    for n_aggs in AGG_COUNTS:
        case = [("sum", cols[i]) for i in range(n_aggs)]
        ops = tuple(op for op, _ in case)
        vals = tuple(v for _, v in case)

        def fused(ops=ops, vals=vals):
            return segops.fused_segreduce(keys, vals, ops, N_KEYS, mask=mask)

        def unfused(case=case):
            # the pre-fusion kernel lowering: funnel + one scatter per
            # aggregate, plus the separate presence pass
            safe = jnp.where(mask > 0, keys, 0)
            accs = []
            for op, v in case:
                vv = jnp.where(mask > 0, v, op_identity(op, v.dtype))
                accs.append(segops.segreduce(safe, vv, N_KEYS, op=op))
            ones = jnp.where(mask > 0, 1, 0).astype(jnp.int32)
            return tuple(accs), segops.segreduce(safe, ones, N_KEYS, op="sum")

        t = _best_interleaved({"fused": fused, "unfused": unfused})
        ratio = t["unfused"] / t["fused"]
        report["fused_segreduce"][f"{n_aggs}agg"] = {
            "fused_s": t["fused"], "unfused_s": t["unfused"], "ratio": ratio,
        }
        report["key_ratios"][f"fused_vs_unfused_{n_aggs}agg"] = ratio
        out.append((f"kernel_fused_segreduce_{n_aggs}agg", t["fused"] * 1e6,
                    f"{ratio:.2f}x_vs_unfused"))


def _compile_counts(report: Dict) -> None:
    """Chunk-kernel jit compile accounting of the partitioned backend on a
    4-aggregate GROUP BY: the fused unit compiles ONE aggregation kernel
    per shape bucket; the per-aggregate path compiles one per aggregate.
    Machine-independent, so gated tightly (lower-is-better) in CI."""
    from repro.backends import CodegenChoices, PartitionedChoices, get_backend
    from repro.data.multiset import Database, Multiset
    from repro.frontends.sql import sql_to_forelem

    rng = np.random.default_rng(7)
    n = 50_000
    db = Database().add(Multiset.from_columns(
        "t",
        k=rng.integers(0, 256, n).astype(np.int32),
        v=rng.integers(-100, 100, n).astype(np.int32),
        w=rng.normal(size=n).astype(np.float32),
    ))
    sql = "SELECT k, SUM(v), SUM(w), MAX(w), MIN(v) FROM t GROUP BY k"
    prog = sql_to_forelem(sql, {"t": ["k", "v", "w"]})
    backend = get_backend("partitioned")
    for label, method in (("fused", "kernel"), ("per_agg", "dense")):
        plan = backend.compile(prog, db, PartitionedChoices(
            base=CodegenChoices(agg_method=method),
            n_partitions=4, schedule="static", partition_field=("t", "k"),
            jit_chunks=True, async_dispatch=False,
        ))
        plan.run()
        rep = plan.runtime_report()["jit"]
        report["compile_counts"][label] = {
            "kernels": rep["kernels"], "buckets": rep["buckets"],
            "compiles": rep["compiles"], "hits": rep["hits"],
        }
        report["key_counts"][f"kernels_{label}_4agg_jit_compiles"] = rep["compiles"]
    fused = report["compile_counts"]["fused"]
    assert fused["compiles"] <= fused["buckets"], (
        f"fused agg kernel recompiled within a bucket: {fused}"
    )


def run() -> List[Tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    out: List[Tuple[str, float, str]] = []
    report: Dict = {
        "n_rows": N_ROWS, "n_keys": N_KEYS,
        "fused_segreduce": {}, "compile_counts": {},
        "key_ratios": {}, "key_counts": {},
    }

    # fused multi-aggregate segreduce vs the per-aggregate path (tentpole)
    _fused_vs_unfused(rng, report, out)
    # partitioned chunk-kernel compile counts: fused vs per-aggregate
    _compile_counts(report)

    # segreduce: group-by count at 4M rows (the Fig.2 hot loop)
    from repro.kernels.segreduce.ref import segreduce_ref

    n, k = 4_000_000, 8192
    keys = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    vals = jnp.ones((n,), jnp.float32)
    f = jax.jit(lambda a, b: segreduce_ref(a, b, k))
    t = _timeit(lambda: f(keys, vals))
    gbps = (n * 8) / t / 1e9
    out.append(("kernel_segreduce_ref_4M", t * 1e6, f"{gbps:.2f}GB/s"))

    with open("BENCH_kernels.json", "w") as fh:
        json.dump(report, fh, indent=2)
    return out


if __name__ == "__main__":
    for name, us, derived in run():
        print(f"{name:<36s} {us:>12.1f}us  {derived}")
