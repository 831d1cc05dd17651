"""Microbenchmark of the segreduce Pallas kernel on one TPU chip.

Times the Mosaic-compiled ``fused_segreduce_pallas`` at ``--rows`` (default
2^25) on each of its paths (the VPU compare sweep and the MXU contraction,
forced in turn) against XLA's ``segment_sum`` and the jnp fused fallback,
checks every result against numpy, and prints one JSON line per case.  Its
numbers are what ``kernel.KEY_TILE``, the paths' crossover
(``kernel._VPU_MAX_TILES``), ``ops.ENGINE_TILE`` and the compiled kernel's
cost coefficients (``planner/cost.py``: ``c_kernel``, ``c_kernel_tile_agg``)
rest on.  Needs a TPU: interpret mode at this size would take hours.

    python benchmarks/chip_segreduce.py [--rows N] [--keys 64,2048,3000]
        [--key-tiles 32,64,128] [--tiles 16384,65536]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from functools import partial
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.segreduce import kernel as K  # noqa: E402
from repro.kernels.segreduce.ops import ENGINE_TILE  # noqa: E402
from repro.kernels.segreduce.ref import fused_segreduce_ref  # noqa: E402

F32_SUM_RTOL = 1e-4


def timeit(f, *args, reps: int = 3):
    """(result, cold ms, best warm ms of ``reps``)."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    cold = (time.perf_counter() - t0) * 1e3
    warm = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        warm = min(warm, (time.perf_counter() - t0) * 1e3)
    return out, cold, warm


@contextmanager
def key_tile(kt: int):
    """Run the kernel with ``kt`` key ids per accumulator block."""
    saved = K.KEY_TILE, K._TILES_PER_ROW, K._TILES_PER_OUT
    K.KEY_TILE = kt
    K._TILES_PER_ROW = K._LANES // kt
    K._TILES_PER_OUT = K._TILES_PER_ROW * K._SUBLANES
    try:
        yield
    finally:
        K.KEY_TILE, K._TILES_PER_ROW, K._TILES_PER_OUT = saved


@contextmanager
def kernel_path(path: str):
    """Run every launch on ``path`` ('vpu' or 'mxu'), whatever its shape."""
    saved = K.kernel_path
    K.kernel_path = lambda ops, dtypes, num_keys: path
    try:
        yield
    finally:
        K.kernel_path = saved


def rel_err(got, want) -> float:
    want = np.asarray(want, np.float64)
    seen = want != 0
    return float(np.max(np.abs(np.asarray(got, np.float64)[seen] - want[seen]) / np.abs(want[seen])))


def kernel_fn(ops, num_keys, tile, with_presence=True):
    return jax.jit(partial(
        K.fused_segreduce_pallas, ops=ops, num_keys=num_keys,
        with_presence=with_presence, tile=tile, interpret=False,
    ))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--keys", default="64,2048,3000", help="key spaces")
    ap.add_argument("--key-tiles", default="64", help="KEY_TILE values (divisors of 128)")
    ap.add_argument("--tiles", default=str(ENGINE_TILE), help="rows per grid step")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    print(json.dumps({"device": dev.device_kind, "rows": args.rows}), flush=True)
    n = args.rows
    rng = np.random.default_rng(args.seed)
    zipf = rng.zipf(1.3, n)
    status = rng.choice(np.array([200, 200, 200, 304, 404, 500], np.int32), n)
    lat = rng.gamma(2.0, 30.0, n).astype(np.float32)
    kb = rng.integers(0, 64, n, dtype=np.int32)
    d_status, d_lat, d_kb = map(jnp.asarray, (status, lat, kb))
    ones = jnp.ones((n,), jnp.int32)
    bad = 0

    def emit(**rec):
        nonlocal bad
        bad += rec.get("ok") is False
        print(json.dumps(rec), flush=True)

    for nk in (int(x) for x in args.keys.split(",")):
        keys = (zipf % nk).astype(np.int32)
        d_keys = jnp.asarray(keys)
        want = np.bincount(keys, minlength=nk)
        want_lat = np.bincount(keys, weights=lat.astype(np.float64), minlength=nk)
        chosen = K.kernel_path(("sum",), [jnp.int32], nk)
        for kt in (int(x) for x in args.key_tiles.split(",")):
            for tile in (int(x) for x in args.tiles.split(",")):
                with key_tile(kt), kernel_path("vpu"):
                    f = kernel_fn(("sum",), nk, tile)
                    (accs, pres), cold, warm = timeit(lambda k, v: f(k, (v,)), d_keys, ones)
                ok = np.array_equal(np.asarray(accs[0]), want) and np.array_equal(np.asarray(pres), want)
                emit(case="kernel_count", path="vpu", chosen=chosen, keys=nk, key_tile=kt,
                     tile=tile, key_tiles=-(-nk // kt), ok=bool(ok), cold_ms=cold, warm_ms=warm)
        with kernel_path("mxu"):
            f = kernel_fn(("sum",), nk, ENGINE_TILE)
            (accs, pres), cold, warm = timeit(lambda k, v: f(k, (v,)), d_keys, ones)
        ok = np.array_equal(np.asarray(accs[0]), want) and np.array_equal(np.asarray(pres), want)
        emit(case="kernel_count", path="mxu", chosen=chosen, keys=nk, tile=ENGINE_TILE,
             key_tiles=-(-nk // K.MXU_KEYS), ok=bool(ok), cold_ms=cold, warm_ms=warm)
        # the BDB aggregation's launch: an f32 SUM and the presence count
        for path in ("vpu", "mxu"):
            with kernel_path(path):
                f = kernel_fn(("sum",), nk, ENGINE_TILE)
                (accs, pres), cold, warm = timeit(lambda k, v: f(k, (v,)), d_keys, d_lat)
            err = rel_err(accs[0], want_lat)
            ok = err < F32_SUM_RTOL and np.array_equal(np.asarray(pres), want)
            emit(case="kernel_f32_sum", path=path, chosen=K.kernel_path(("sum",), [jnp.float32], nk),
                 keys=nk, ok=bool(ok), rel_err=err, cold_ms=cold, warm_ms=warm)
        seg = jax.jit(lambda k, v, nk=nk: jax.ops.segment_sum(v, k, num_segments=nk))
        got, cold, warm = timeit(seg, d_keys, ones)
        emit(case="segment_sum_count", keys=nk, ok=bool(np.array_equal(np.asarray(got), want)),
             cold_ms=cold, warm_ms=warm)
        ref = jax.jit(lambda k, v, nk=nk: fused_segreduce_ref(k, (v,), ("sum",), nk))
        (accs, _), cold, warm = timeit(ref, d_keys, ones)
        emit(case="fallback_count", keys=nk, ok=bool(np.array_equal(np.asarray(accs[0]), want)),
             cold_ms=cold, warm_ms=warm)

    # f32 SUM/MAX/MIN plus presence by status, masked (the fused status group)
    f = kernel_fn(("sum", "max", "min"), 501, ENGINE_TILE)
    (accs, pres), cold, warm = timeit(
        lambda k, v, m: f(k, (v, v, v), mask=m), d_status, d_lat, d_kb < 32
    )
    sel = kb < 32
    s64 = np.zeros(501)
    np.add.at(s64, status[sel], lat[sel].astype(np.float64))
    mx = np.full(501, -np.inf, np.float32)
    np.maximum.at(mx, status[sel], lat[sel])
    mn = np.full(501, np.inf, np.float32)
    np.minimum.at(mn, status[sel], lat[sel])
    ok = (
        np.allclose(np.asarray(accs[0]), s64, rtol=F32_SUM_RTOL)
        and np.array_equal(np.asarray(accs[1]), mx)
        and np.array_equal(np.asarray(accs[2]), mn)
        and np.array_equal(np.asarray(pres), np.bincount(status[sel], minlength=501))
    )
    emit(case="kernel_f32_sum_max_min_masked", path=K.kernel_path(("sum", "max", "min"), [jnp.float32] * 3, 501),
         keys=501, key_tiles=-(-501 // K.KEY_TILE),
         ok=bool(ok), cold_ms=cold, warm_ms=warm)

    # int32 SUM(kb) by url (3000 keys), no presence
    url = (zipf % 3000).astype(np.int32)
    want = np.bincount(url, weights=kb, minlength=3000).astype(np.int64)
    for path in ("vpu", "mxu"):
        with kernel_path(path):
            f = kernel_fn(("sum",), 3000, ENGINE_TILE, with_presence=False)
            (accs, _), cold, warm = timeit(lambda k, v: f(k, (v,)), jnp.asarray(url), d_kb)
        emit(case="kernel_i32_sum", path=path, keys=3000,
             ok=bool(np.array_equal(np.asarray(accs[0]), want)), cold_ms=cold, warm_ms=warm)
    emit(case="peak", peak_bytes_in_use=(dev.memory_stats() or {}).get("peak_bytes_in_use"))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
