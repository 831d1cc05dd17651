# Per-kernel validation: shape/dtype sweeps, Pallas (interpret mode) vs the
# pure-jnp oracle, the fused multi-aggregate differential matrix, plus
# hypothesis property tests on segreduce (skipped if hypothesis is absent —
# the matrix below must run regardless).
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax.numpy as jnp

from repro.kernels.segreduce.kernel import (
    acc_dtype,
    fused_segreduce_pallas,
    kernel_path,
    op_identity,
    segreduce_pallas,
)
from repro.kernels.segreduce.ref import fused_segreduce_ref, segreduce_ref

# ---------------------------------------------------------------------------
# segreduce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [8, 100, 1024, 5000])
@pytest.mark.parametrize("k", [1, 7, 128, 1000])
@pytest.mark.parametrize("op", ["sum", "max", "min"])
def test_segreduce_sweep(rng, n, k, op):
    keys = jnp.asarray(rng.integers(0, k, n), jnp.int32)
    vals = jnp.asarray(rng.normal(size=n), jnp.float32)
    got = segreduce_pallas(keys, vals, k, op=op, interpret=True)
    want = segreduce_ref(keys, vals, k, op=op)
    if op in ("max", "min"):
        # empty segments: kernel and ref both yield the ∓inf identity
        mask = np.asarray(segreduce_ref(keys, jnp.ones_like(vals), k)) > 0
        np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask], rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["sum", "max", "min"])
@pytest.mark.parametrize(
    "dtype,num_keys",
    [
        pytest.param(jnp.float32, 33, id="float32"),
        pytest.param(jnp.bfloat16, 33, id="bfloat16"),
        pytest.param(jnp.int32, 33, id="int32"),
        # sums over 2,048 keys (not a multiple of 16) take the MXU path;
        # int32 values span the whole range, negative ones and sums that wrap
        pytest.param(jnp.float32, 2100, id="float32-2100keys"),
        pytest.param(jnp.bfloat16, 2100, id="bfloat16-2100keys"),
        pytest.param(jnp.int32, 2100, id="int32-2100keys"),
    ],
)
def test_segreduce_dtypes(rng, dtype, num_keys, op):
    """Input dtype is PRESERVED (int32 in → int32 out), with dtype-correct
    identities — int MIN/MAX use the iinfo extremes, not a float sentinel."""
    n = 500 if num_keys == 33 else 6000
    keys = jnp.asarray(rng.integers(0, num_keys, n), jnp.int32)
    if num_keys == 33:
        vals = jnp.asarray(rng.integers(0, 10, n)).astype(dtype)
    elif dtype == jnp.int32:
        vals = jnp.asarray(rng.integers(-(2**31), 2**31, n, dtype=np.int64).astype(np.int32))
    else:
        vals = jnp.asarray(rng.normal(size=n)).astype(dtype)
    got = segreduce_pallas(keys, vals, num_keys, op=op, interpret=True)
    assert got.dtype == jnp.dtype(dtype)
    assert segreduce_ref(keys, vals, num_keys, op=op).dtype == jnp.dtype(dtype)
    # the kernel accumulates bf16 in f32 (acc_dtype) and rounds once
    want = segreduce_ref(keys, vals.astype(acc_dtype(dtype)), num_keys, op=op).astype(dtype)
    if dtype == jnp.int32:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64), rtol=1e-2, atol=1e-2
    )


@pytest.mark.parametrize(
    "ops,dtypes,num_keys,path",
    [
        (("sum",), ("float32",), 2048, "mxu"),
        (("sum", "sum"), ("int32", "bfloat16"), 3000, "mxu"),
        (("sum",), ("int32",), 1 << 20, "mxu"),
        (("sum",), ("float32",), 64, "vpu"),  # one 64-key tile
        (("sum",), ("float32",), 1, "vpu"),
        (("sum",), ("int32",), 192, "vpu"),  # three tiles: the sweep is cheaper
        (("sum",), ("int32",), 193, "mxu"),
        (("max",), ("float32",), 2048, "vpu"),
        (("min",), ("int32",), 3000, "vpu"),
        (("sum", "max"), ("float32", "float32"), 2048, "vpu"),
        (("sum",), ("float16",), 2048, "vpu"),  # not a dtype the MXU takes exactly
    ],
)
def test_kernel_path_contracts_wide_sum_groups_on_the_mxu(ops, dtypes, num_keys, path):
    assert kernel_path(ops, [jnp.dtype(d) for d in dtypes], num_keys) == path


@pytest.mark.parametrize("value", [255, -1])
def test_mxu_byte_counts_exact_at_a_full_step(value):
    """A whole 2^16-row grid step of one key: each byte term's count reaches
    2^16 x 255, just under 2^24, and stays exact in f32; the int32 sum
    wraps as an int32 add does (-1 is four bytes of 255)."""
    n, num_keys = 1 << 16, 2100
    keys = jnp.full((n,), 2099, jnp.int32)
    vals = jnp.full((n,), value, jnp.int32)
    (acc,), pres = fused_segreduce_pallas(
        keys, (vals,), ("sum",), num_keys, tile=1 << 16, interpret=True
    )
    assert int(acc[2099]) == np.int32(np.int64(value) * n)
    assert int(pres[2099]) == n
    assert not np.asarray(acc[:2099]).any() and not np.asarray(pres[:2099]).any()


def test_mxu_non_finite_values_stay_in_their_key():
    """+inf, -inf and NaN sum as IEEE adds do, in their own key only: a
    NaN times the one-hot's zeros must not reach the keys beside it, nor a
    masked row's NaN any key.  A finite value above bf16's largest (key
    10) splits into finite terms too."""
    num_keys = 2100
    keys = np.arange(4000, dtype=np.int32) % num_keys
    vals = np.ones(4000, np.float32)
    odd = {5: [np.inf], 6: [-np.inf], 7: [np.nan], 8: [np.inf, -np.inf], 9: [np.inf, np.inf],
           10: [np.finfo(np.float32).max]}
    at = 0
    for k, vs in odd.items():
        for v in vs:
            keys[at], vals[at] = k, v
            at += 1
    mask = np.ones(4000, bool)
    keys[at], vals[at], mask[at] = 17, np.nan, False
    (acc,), _ = fused_segreduce_pallas(
        jnp.asarray(keys), (jnp.asarray(vals),), ("sum",), num_keys,
        mask=jnp.asarray(mask), interpret=True,
    )
    (want,), _ = fused_segreduce_ref(
        jnp.asarray(keys), (jnp.asarray(vals),), ("sum",), num_keys, mask=jnp.asarray(mask)
    )
    got = np.asarray(acc)
    assert kernel_path(("sum",), [jnp.float32], num_keys) == "mxu"
    assert np.isposinf(got[5]) and np.isneginf(got[6]) and np.isnan(got[7:9]).all()
    assert np.isposinf(got[9])
    np.testing.assert_allclose(np.delete(got, [5, 6, 7, 8, 9]), np.delete(np.asarray(want), [5, 6, 7, 8, 9]))


def test_segreduce_int_extremes_identity():
    """Empty int32 MIN/MAX segments hold the iinfo identity, and negative
    extremes survive (a -inf/f32 sentinel would corrupt both)."""
    keys = jnp.asarray([0, 0, 2], jnp.int32)
    vals = jnp.asarray([-(2**31) + 5, 7, -3], jnp.int32)
    mx = segreduce_pallas(keys, vals, 3, op="max", interpret=True)
    mn = segreduce_pallas(keys, vals, 3, op="min", interpret=True)
    assert mx.dtype == jnp.int32 and mn.dtype == jnp.int32
    assert np.asarray(mx).tolist() == [7, np.iinfo(np.int32).min, -3]
    assert np.asarray(mn).tolist() == [-(2**31) + 5, np.iinfo(np.int32).max, -3]


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(1, 2000), k=st.integers(1, 300), seed=st.integers(0, 99))
    def test_property_segreduce_equals_ref(n, k, seed):
        rng = np.random.default_rng(seed)
        keys = jnp.asarray(rng.integers(0, k, n), jnp.int32)
        vals = jnp.asarray(rng.normal(size=n), jnp.float32)
        got = segreduce_pallas(keys, vals, k, interpret=True)
        want = segreduce_ref(keys, vals, k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("tile", [2048, 8192])
def test_fused_blocks_groups_and_key_tiles(rng, dtype, tile):
    """Several row blocks, several (8, 128) row groups per block, several
    key tiles and a ragged tail: every grid and loop boundary of the
    lane-dense kernel against the jnp fallback."""
    n, num_keys = 20_000, 300
    keys = rng.integers(-2, num_keys + 2, n).astype(np.int32)  # out-of-range keys hit nothing
    if dtype == "int32":
        vals = rng.integers(-1000, 1000, n).astype(np.int32)
    else:
        vals = rng.normal(size=n).astype(np.float32)
    mask = rng.integers(0, 3, n) > 0
    ops = ("sum", "max", "min")
    got, got_pres = fused_segreduce_pallas(
        jnp.asarray(keys), (jnp.asarray(vals),) * 3, ops, num_keys,
        mask=jnp.asarray(mask), tile=tile, interpret=True,
    )
    inside = (keys >= 0) & (keys < num_keys)
    want, want_pres = fused_segreduce_ref(
        jnp.asarray(np.where(inside, keys, 0)), (jnp.asarray(vals),) * 3, ops, num_keys,
        mask=jnp.asarray(mask & inside),
    )
    np.testing.assert_array_equal(np.asarray(got_pres), np.asarray(want_pres))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("num_keys", [64, 1025, 2500])
def test_fused_output_spans_key_rows_and_blocks(rng, num_keys):
    """The kernel folds each key tile's lane partials into lanes of a dense
    (keys / 128, 128) output, 16 key tiles per (8, 128) block: keys in
    both halves of a row, in several rows and blocks, and a ragged last
    tile all land where the jnp fallback puts them."""
    n = 3000
    keys = rng.integers(0, num_keys, n).astype(np.int32)
    keys[:3] = [0, num_keys - 1, num_keys // 2]
    vals = rng.integers(-1000, 1000, n).astype(np.int32)
    ops = ("sum", "max", "min")
    got, got_pres = fused_segreduce_pallas(
        jnp.asarray(keys), (jnp.asarray(vals),) * 3, ops, num_keys, tile=1024, interpret=True,
    )
    want, want_pres = fused_segreduce_ref(jnp.asarray(keys), (jnp.asarray(vals),) * 3, ops, num_keys)
    np.testing.assert_array_equal(np.asarray(got_pres), np.asarray(want_pres))
    for g, w in zip(got, want):
        assert g.shape == (num_keys,)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize(
    "backend,env,want",
    [
        ("tpu", "auto", "compiled"),
        ("tpu", "1", "compiled"),
        ("tpu", "0", RuntimeError),  # no silent jnp fallback on the chip
        ("cpu", "auto", "off"),
        ("cpu", "1", "interpret"),
        ("cpu", "0", "off"),
        ("cpu", "bogus", ValueError),
    ],
)
def test_pallas_mode(monkeypatch, backend, env, want):
    from repro.kernels.segreduce import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: backend)
    monkeypatch.setenv("REPRO_PALLAS", env)
    if isinstance(want, str):
        assert ops.pallas_mode() == want
    else:
        with pytest.raises(want):
            ops.pallas_mode()


# ---------------------------------------------------------------------------
# fused multi-aggregate segreduce: the differential matrix
#
# Query-level ops {SUM, COUNT, MIN, MAX, AVG} × value dtypes {int32, f32} ×
# {unfiltered, filtered} × {empty table, empty groups, single tile,
# multi-tile}, for BOTH implementations (Pallas interpret mode and the
# pure-jnp fused fallback) against a row-loop numpy oracle; plus
# partial-merge associativity of the multi-accumulator state.
# ---------------------------------------------------------------------------

# (n rows, num_keys, key range) — TILE=1024 ⇒ multi_tile spans 5 row tiles,
# and empty_groups leaves keys [8, 64) with no rows at all.  The sums of the
# last two take the MXU path: one 2,048-key tile, then two, over key spaces
# that are no multiple of 16
_SHAPES = {
    "empty_table": (0, 16, 16),
    "empty_groups": (200, 64, 8),
    "single_tile": (300, 16, 16),
    "multi_tile": (5000, 16, 16),
    "mxu_one_key_tile": (3000, 1000, 1000),
    "mxu_key_tiles": (5000, 3000, 3000),
}

_MERGE_NP = {"sum": np.add, "max": np.maximum, "min": np.minimum}


def _query_lowering(qop, vals_np):
    """Lower one query-level aggregate to kernel (columns, ops), matching
    the SQL frontend: COUNT is a sum of ones, AVG a SUM/COUNT pair."""
    ones = np.ones(vals_np.shape[0], np.int32)
    if qop == "SUM":
        return [vals_np], ["sum"]
    if qop == "COUNT":
        return [ones], ["sum"]
    if qop == "MIN":
        return [vals_np], ["min"]
    if qop == "MAX":
        return [vals_np], ["max"]
    if qop == "AVG":
        return [vals_np, ones], ["sum", "sum"]
    raise AssertionError(qop)


def _oracle(keys, vals, op, mask, num_keys):
    """Row-loop numpy oracle: per-group reduction with op identities."""
    out = np.full(num_keys, op_identity(op, vals.dtype), vals.dtype)
    for key, val, m in zip(keys, vals, mask):
        if not m:
            continue
        if op == "sum":
            out[key] += val
        elif op == "max":
            out[key] = max(out[key], val)
        else:
            out[key] = min(out[key], val)
    return out


def _matrix_inputs(rng, shape, dtype, filtered):
    n, num_keys, key_range = _SHAPES[shape]
    keys = rng.integers(0, key_range, n).astype(np.int32)
    if dtype == "int32":
        vals = rng.integers(-50, 50, n).astype(np.int32)
    else:
        vals = rng.normal(size=n).astype(np.float32)
    mask = rng.integers(0, 2, n).astype(bool) if filtered else np.ones(n, bool)
    return keys, vals, mask, num_keys


def _run_fused(impl, keys, values, ops, num_keys, mask):
    fn = fused_segreduce_pallas if impl == "pallas" else fused_segreduce_ref
    kwargs = {"interpret": True} if impl == "pallas" else {}
    return fn(
        jnp.asarray(keys),
        tuple(jnp.asarray(v) for v in values),
        tuple(ops),
        num_keys,
        mask=jnp.asarray(mask),
        **kwargs,
    )


@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("filtered", [False, True], ids=["unfiltered", "filtered"])
@pytest.mark.parametrize("dtype", ["int32", "float32"])
@pytest.mark.parametrize("qop", ["SUM", "COUNT", "MIN", "MAX", "AVG"])
@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_fused_differential_matrix(rng, impl, qop, dtype, filtered, shape):
    keys, vals, mask, num_keys = _matrix_inputs(rng, shape, dtype, filtered)
    cols, ops = _query_lowering(qop, vals)
    accs, pres = _run_fused(impl, keys, cols, ops, num_keys, mask)

    pres_np = np.array([np.sum((keys == g) & mask) for g in range(num_keys)])
    np.testing.assert_array_equal(np.asarray(pres), pres_np)
    for col, op, acc in zip(cols, ops, accs):
        want = _oracle(keys, col, op, mask, num_keys)
        got = np.asarray(acc)
        assert got.dtype == col.dtype, (impl, qop, got.dtype, col.dtype)
        np.testing.assert_allclose(
            got.astype(np.float64), want.astype(np.float64), rtol=1e-5, atol=1e-5
        )
    if qop == "AVG":  # the pair the frontend divides: sum / count where count > 0
        s, c = np.asarray(accs[0], np.float64), np.asarray(accs[1], np.float64)
        avg = np.divide(s, c, out=np.zeros_like(s), where=c > 0)
        want_avg = np.zeros(num_keys)
        for g in range(num_keys):
            sel = vals[(keys == g) & mask]
            if len(sel):
                want_avg[g] = sel.astype(np.float64).mean()
        np.testing.assert_allclose(avg, want_avg, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_fused_multi_aggregate_mixed_dtypes(rng, impl):
    """One launch, four aggregates over distinct columns and mixed dtypes —
    the whole-query shape the engine actually emits."""
    n, num_keys = 4000, 48
    keys = rng.integers(0, num_keys, n).astype(np.int32)
    vi = rng.integers(-100, 100, n).astype(np.int32)
    vf = rng.normal(size=n).astype(np.float32)
    mask = rng.integers(0, 4, n) > 0
    cols = [vf, vi, vi, vf]
    ops = ["sum", "sum", "min", "max"]
    accs, pres = _run_fused(impl, keys, cols, ops, num_keys, mask)
    for col, op, acc in zip(cols, ops, accs):
        want = _oracle(keys, col, op, mask, num_keys)
        assert np.asarray(acc).dtype == col.dtype
        np.testing.assert_allclose(
            np.asarray(acc, np.float64), want.astype(np.float64), rtol=1e-5, atol=1e-5
        )
    np.testing.assert_array_equal(
        np.asarray(pres), np.bincount(keys[mask], minlength=num_keys)
    )


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
@pytest.mark.parametrize("n_chunks", [1, 3, 8])
def test_fused_partial_merge_associativity(rng, impl, n_chunks):
    """Chunked partial merge (the partitioned runtime's reduction) is
    equivalent to one whole-table pass: split rows into K chunks, run the
    fused kernel per chunk, merge each accumulator under its own op and
    presence under +."""
    n, num_keys = 3000, 32
    keys = rng.integers(0, num_keys, n).astype(np.int32)
    vi = rng.integers(-100, 100, n).astype(np.int32)
    vf = rng.normal(size=n).astype(np.float32)
    mask = rng.integers(0, 3, n) > 0
    cols = [vi, vf, vi]
    ops = ["sum", "max", "min"]

    whole_accs, whole_pres = _run_fused(impl, keys, cols, ops, num_keys, mask)

    bounds = np.linspace(0, n, n_chunks + 1).astype(int)
    accs = [None] * len(ops)
    pres = None
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        part, ppres = _run_fused(
            impl, keys[lo:hi], [c[lo:hi] for c in cols], ops, num_keys, mask[lo:hi]
        )
        for i, op in enumerate(ops):
            a = np.asarray(part[i])
            accs[i] = a if accs[i] is None else _MERGE_NP[op](accs[i], a)
        p = np.asarray(ppres)
        pres = p if pres is None else pres + p
    for i, (op, col) in enumerate(zip(ops, cols)):
        assert accs[i].dtype == col.dtype
        np.testing.assert_allclose(
            accs[i].astype(np.float64),
            np.asarray(whole_accs[i], np.float64),
            rtol=1e-5, atol=1e-5,
        )
    np.testing.assert_array_equal(pres, np.asarray(whole_pres))


# ---------------------------------------------------------------------------
# fused kernel ↔ engine wiring
# ---------------------------------------------------------------------------


def _kernel_db(rng, n=20000):
    from repro.data.multiset import Database, Multiset

    return Database().add(
        Multiset.from_columns(
            "t",
            k=rng.integers(0, 50, n).astype(np.int32),
            v=rng.integers(-100, 100, n).astype(np.int32),
            w=rng.normal(size=n).astype(np.float32),
        )
    )


_MULTI_AGG_SQL = "SELECT k, SUM(v), MIN(v), MAX(w), COUNT(k), AVG(w) FROM t GROUP BY k"


def _rows_close(a, b, tol=1e-3):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        for x, y in zip(ra, rb):
            assert abs(float(x) - float(y)) < tol, (ra, rb)


@pytest.mark.parametrize("where", ["", " WHERE v > 10"])
def test_engine_kernel_matches_dense_monolithic(rng, where):
    """agg_method='kernel' (one fused launch for the whole aggregate group)
    is row-identical to 'dense' through the full SQL lowering."""
    from repro.backends.jax_vec import CodegenChoices, Plan
    from repro.core.transforms import canonicalize_array_names
    from repro.frontends.sql import sql_to_forelem

    db = _kernel_db(rng)
    sql = _MULTI_AGG_SQL.replace(" GROUP BY", where + " GROUP BY")
    p = canonicalize_array_names(sql_to_forelem(sql, {"t": ["k", "v", "w"]}))
    kplan = Plan(p, db, CodegenChoices(agg_method="kernel"))
    # the whole query's aggregates land in ONE fused group, loudly
    assert [len(g) for g in kplan.lowering.fused_groups] == [6]
    assert kplan.lowering.method_notes == []
    _rows_close(
        sorted(Plan(p, db, CodegenChoices(agg_method="dense")).run()["R"]),
        sorted(kplan.run()["R"]),
    )


@pytest.mark.parametrize("jit_chunks,async_dispatch", [(True, False), (False, False), (True, True)])
def test_engine_kernel_matches_dense_partitioned(rng, jit_chunks, async_dispatch):
    """The partitioned runtime dispatches the fused group as ONE unit per
    chunk and partial-merges the multi-accumulator state."""
    from repro.backends.jax_vec import CodegenChoices, Plan
    from repro.backends.partitioned import PartitionedChoices, PartitionedPlan
    from repro.core.transforms import canonicalize_array_names
    from repro.frontends.sql import sql_to_forelem

    db = _kernel_db(rng)
    p = canonicalize_array_names(sql_to_forelem(_MULTI_AGG_SQL, {"t": ["k", "v", "w"]}))
    want = sorted(Plan(p, db, CodegenChoices(agg_method="dense")).run()["R"])
    plan = PartitionedPlan(
        p, db,
        PartitionedChoices(
            base=CodegenChoices(agg_method="kernel"), n_partitions=4,
            jit_chunks=jit_chunks, async_dispatch=async_dispatch,
        ),
    )
    _rows_close(want, sorted(plan.run()["R"]))
    agg_ds = [d for d in plan.dispatch_log if d.op.startswith("agg:")]
    assert agg_ds and all(d.fused and d.n_aggs == 6 for d in agg_ds)
    # run 2 exercises the memoized presence path on the fused kernel
    _rows_close(want, sorted(plan.run()["R"]))


def test_onehot_min_fallback_is_loud(rng):
    """Satellite: an op the requested method cannot evaluate downgrades to
    'dense' — with a method_notes entry the optimizer surfaces into the
    pass trace and Decision.rejections, never silently."""
    from repro.backends.jax_vec import CodegenChoices, Plan
    from repro.core import OptimizeOptions, optimize
    from repro.core.transforms import canonicalize_array_names
    from repro.frontends.sql import sql_to_forelem

    db = _kernel_db(rng, n=2000)
    sql = "SELECT k, MIN(v) FROM t GROUP BY k"
    p = canonicalize_array_names(sql_to_forelem(sql, {"t": ["k", "v", "w"]}))
    plan = Plan(p, db, CodegenChoices(agg_method="onehot"))
    assert any("onehot" in note and "'min'" in note for note in plan.lowering.method_notes)
    # ... and the downgraded execution is still correct
    _rows_close(
        sorted(Plan(p, db, CodegenChoices(agg_method="dense")).run()["R"]),
        sorted(plan.run()["R"]),
    )
    res = optimize(p, db, OptimizeOptions(agg_method="onehot", trace=True))
    assert any("aggregation-method fallback" in t for t in res.trace)
