# Pallas TPU kernels: segmented (group-by) aggregation, single-op and
# fused multi-aggregate.
#
# TPU adaptation of the paper's hash-table index-set materialization
# (Fig. 1 bottom): scalar hashing is hostile to the VPU, so no row looks its
# key up.  Two paths evaluate a launch; ``kernel_path`` picks one from the
# launch's ops, dtypes and key space, and the engine's lowering asks it too.
#
# VPU path (any op): each key id owns a row of a VMEM-resident accumulator
# block and every input row is compared against a whole tile of key ids at
# once.  A hit selects the row's value, a miss the op's identity, and the
# accumulator folds it in with the op itself (add / max / min) — one masked
# VPU reduction for every op and dtype.  Its work is rows x key tiles.
#
# MXU path (sum groups of f32, bf16 and int32 columns over more than a few
# key tiles): a key splits as ``hi * 16 + lo``, and a grid step contracts
# its rows on the MXU: the one-hot of ``lo`` times each value term (16 x
# rows) against the one-hot of ``hi`` (rows x 128), so one 2,048-key tile
# costs one matmul per 128 rows.  Both one-hots are built in VMEM per 128
# rows and never reach HBM.  The MXU multiplies bf16, so a value enters as
# terms that are exact in bf16: an f32 as three (v = v1 + v2 + v3), a bf16
# as one, an int32 as its four bytes (0..255).  Every product is exact and
# accumulates in f32, so an f32 sum differs from the VPU path's only in the
# order of its additions (the steps' partials fold in with a compensated
# add), and an int32 sum recombines its bytes' exact per-step counts with
# int32 shifts and adds, wrapping as the VPU path's adds wrap.  Presence is
# the one-hot of ``lo`` itself.  A non-finite value would turn the zeros of
# the one-hot of ``hi`` into NaNs, so it enters as 0, and a step that holds
# one counts its +inf/NaN and -inf/NaN rows per key in a second contraction;
# the last step turns those counts into inf, -inf or NaN, as IEEE adds do.
#
# The fused kernel evaluates a whole query's aggregate group in ONE
# pallas_call: every aggregate's accumulator plus the group-presence
# histogram from one pass over the rows.  The filter mask is folded into
# the keys before the call (a masked row gets key -1, which matches no key
# id: on the MXU path its ``hi`` is -1), so masked and padded rows
# contribute each op's identity by construction.
#
# Layout: every column is viewed lane-dense as (rows, 128) — one input block
# is (block_rows, 128), so HBM and VMEM hold the data at its own size (an
# (N, 1) column would take a whole 128-lane row per element).  The grid is
# (key tiles, row blocks): the row axis is the reduction, so each key
# tile's accumulators stay resident while every row block streams past them
# (TPU grids run sequentially, so the read-modify-write accumulation is
# race-free).  On the VPU path key ids run along sublanes: a VMEM
# accumulator is (KEY_TILE, 128), one key id per row and one lane partial
# per lane; after the last row block the kernel folds the lane partials and
# writes the tile's keys along the lanes of a dense (keys / 128, 128)
# output, 4 bytes a key.  On the MXU path a key tile's accumulator is its
# (16, 128) output block itself, key ``hi * 16 + lo`` at [lo, hi % 128].
# Integer and float accumulators are preserved; sub-f32 floats accumulate
# in f32 and are cast back.
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Ops the segmented-aggregation kernels evaluate (engine spelling '+' is
# mapped to 'sum' by backends/jax_vec).  COUNT and AVG lower to these at
# the frontend: COUNT is a sum of ones, AVG a sum/count pair.
OPS = ("sum", "max", "min")


def op_identity(op: str, dtype) -> jnp.ndarray:
    """Identity element of ``op`` for ``dtype`` — what masked/padded rows
    contribute so they can never perturb a segment.  Dtype-correct: int
    MIN/MAX use the iinfo extremes (a float -inf sentinel is *wrong* for
    integer accumulators), float MIN/MAX use ±inf."""
    dt = jnp.dtype(dtype)
    if op == "sum":
        return jnp.zeros((), dt)
    if op not in ("max", "min"):
        raise ValueError(f"unknown segreduce op {op!r}")
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return jnp.asarray(info.min if op == "max" else info.max, dt)
    return jnp.asarray(-jnp.inf if op == "max" else jnp.inf, dt)


def acc_dtype(dtype) -> jnp.dtype:
    """Accumulator dtype for a value column: preserved, except sub-f32
    floats (bf16/f16), which accumulate in f32 for precision and are cast
    back at the end."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating) and dt.itemsize < 4:
        return jnp.dtype(jnp.float32)
    return dt


_LANES = 128
_SUBLANES = 8
# key ids per accumulator block (8 vregs of (8, 128) per accumulator): on a
# v5e, 64 ran a 3000-key COUNT over 2^25 rows faster than 32 or 128
# (benchmarks/chip_segreduce.py).  Every key tile streams all the rows once.
KEY_TILE = 64
_TILES_PER_ROW = _LANES // KEY_TILE  # key tiles per 128-key output row
_TILES_PER_OUT = _TILES_PER_ROW * _SUBLANES  # ... per (8, 128) output block

# MXU path: key = hi * _LO + lo, one MXU key tile spans 128 values of hi
_LO_BITS = 4
_LO = 1 << _LO_BITS
MXU_KEYS = _LANES * _LO
# bf16 terms each value dtype enters the MXU as (and only these dtypes)
_MXU_TERMS = {"float32": 3, "bfloat16": 1, "int32": 4}
# rows one MXU grid step may stream: a step's count of an int32 byte term
# (0..255) is exact in f32 while rows x 255 < 2^24, i.e. up to 65,793 rows
_MXU_STEP_ROWS = 1 << 16
# the VPU path wins up to this many 64-key tiles: on a v5e, over 2^25 rows,
# one 2,048-key MXU tile took 8.6-9.1 ms, the VPU sweep 5.3 ms at two 64-key
# tiles and 9.5-9.6 ms at four (benchmarks/chip_segreduce.py)
_VPU_MAX_TILES = 3
_ROWS_NT = (((1,), (1,)), ((), ()))  # contract two operands' lane (row) axes


def _combine(op: str, acc, x):
    if op == "sum":
        return acc + x
    return jnp.maximum(acc, x) if op == "max" else jnp.minimum(acc, x)


def _reduce(op: str, x: jnp.ndarray, axis: int) -> jnp.ndarray:
    if op == "sum":
        return x.sum(axis=axis, keepdims=True)
    return (x.max if op == "max" else x.min)(axis=axis, keepdims=True)


def _fused_kernel(*refs, ops: Tuple[str, ...], n_vals: int, n_groups: int):
    # ``ops`` holds every accumulator's op: the first ``n_vals`` fold a value
    # column, a last extra one (presence, a sum) counts the hits
    n_accs = len(ops)
    keys_ref = refs[0]
    vals_refs = refs[1 : 1 + n_vals]
    out_refs = refs[1 + n_vals : 1 + n_vals + n_accs]
    acc_refs = refs[1 + n_vals + n_accs :]  # VMEM lane partials, (KEY_TILE, 128)
    j, i = pl.program_id(0), pl.program_id(1)
    idents = [op_identity(op, a.dtype) for op, a in zip(ops, acc_refs)]

    @pl.when(i == 0)
    def _init():
        for a_ref, ident in zip(acc_refs, idents):
            a_ref[...] = jnp.full_like(a_ref, ident)

    @pl.when((i == 0) & (j % _TILES_PER_OUT == 0))
    def _init_out():
        for o_ref, ident in zip(out_refs, idents):
            o_ref[...] = jnp.full_like(o_ref, ident)

    key_ids = j * KEY_TILE + jax.lax.broadcasted_iota(jnp.int32, (KEY_TILE, _LANES), 0)

    def group(g, accs):
        # one aligned (8, 128) load per column, then its 8 rows one by one:
        # each row is compared against the whole key tile at once
        r0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        keys = keys_ref[pl.ds(r0, _SUBLANES), :]
        vals = [v_ref[pl.ds(r0, _SUBLANES), :] for v_ref in vals_refs]
        accs = list(accs)
        for s in range(_SUBLANES):
            hit = key_ids == keys[s : s + 1, :]  # (KEY_TILE, 128)
            for a in range(n_vals):
                contrib = jnp.where(hit, vals[a][s : s + 1, :], idents[a])
                accs[a] = _combine(ops[a], accs[a], contrib)
            if n_accs > n_vals:
                accs[n_vals] = accs[n_vals] + jnp.where(hit, 1, 0).astype(jnp.int32)
        return tuple(accs)

    accs = jax.lax.fori_loop(0, n_groups, group, tuple(a[...] for a in acc_refs))
    for a_ref, a in zip(acc_refs, accs):
        a_ref[...] = a

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        # fold each key's 128 lane partials, then move the key tile from
        # sublanes onto lanes: key k of tile j lands in lane
        # (j % _TILES_PER_ROW) * KEY_TILE + k of output row j // _TILES_PER_ROW
        sub = jax.lax.broadcasted_iota(jnp.int32, (KEY_TILE, _LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (KEY_TILE, _LANES), 1)
        on_lane = lane == sub + (j % _TILES_PER_ROW) * KEY_TILE
        out_row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
        on_row = out_row == (j // _TILES_PER_ROW) % _SUBLANES
        for op, a, o_ref, ident in zip(ops, accs, out_refs, idents):
            per_key = _reduce(op, a, axis=1)  # (KEY_TILE, 1)
            row = _reduce(op, jnp.where(on_lane, per_key, ident), axis=0)  # (1, 128)
            o_ref[...] = _combine(op, o_ref[...], jnp.where(on_row, row, ident))


def kernel_path(ops: Sequence[str], dtypes: Sequence, num_keys: int) -> str:
    """``'mxu'`` or ``'vpu'``: the path a launch aggregating columns of
    ``dtypes`` under ``ops`` (presence, an int32 sum, implied) into
    ``num_keys`` keys takes.  The MXU contracts sums alone, of the dtypes
    it takes exactly; over a few key tiles the VPU's sweep is cheaper."""
    if not all(op == "sum" for op in ops):
        return "vpu"
    if not all(jnp.dtype(dt).name in _MXU_TERMS for dt in dtypes):
        return "vpu"
    return "mxu" if -(-num_keys // KEY_TILE) > _VPU_MAX_TILES else "vpu"


def _bf16_high(x):
    """``x`` cut to its sign, exponent and top 7 mantissa bits: exact in
    bf16, and never rounded up past bf16's largest to inf."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32) & jnp.int32(-(1 << 16))
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _mxu_terms(kind: str, v) -> list:
    """Terms of an (8, 128) value group, each exact in bf16, that sum to it:
    three for f32 (non-finite values enter as 0), one for a bf16 value
    (held as f32), four bytes for int32.  An f32 under 2^-110 in magnitude
    loses the bits of its terms that fall below f32's normal range (2^-126),
    which the device flushes to zero."""
    if kind == "int32":
        return [((v >> (8 * b)) & 255).astype(jnp.float32) for b in range(4)]
    v = jnp.where(jnp.abs(v) < jnp.inf, v, 0.0)
    if kind == "bfloat16":
        return [v]
    v1 = _bf16_high(v)  # 24 mantissa bits: 8 each in v1, v2 and v3
    r = v - v1
    v2 = _bf16_high(r)
    return [v1, v2, r - v2]


def _mxu_kernel(*refs, kinds: Tuple[str, ...], with_presence: bool, n_groups: int):
    n_vals = len(kinds)
    keys_ref = refs[0]
    vals_refs = refs[1 : 1 + n_vals]
    out_refs = refs[1 + n_vals : 1 + 2 * n_vals + with_presence]
    # per float value: its Kahan compensation (16, 128) and its non-finite
    # counts (32, 128): rows [0, 16) +inf or NaN, [16, 32) -inf or NaN
    floats = [a for a, k in enumerate(kinds) if k != "int32"]
    scratch = refs[1 + 2 * n_vals + with_presence :]
    comp = dict(zip(floats, scratch[0::2]))
    nonfinite = dict(zip(floats, scratch[1::2]))
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        for r in (*out_refs, *scratch):
            r[...] = jnp.zeros_like(r)

    def rows(g):  # the lane rows of (8, 128) group g of the step
        return pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES), _SUBLANES)

    def contract(terms_of, n_terms):
        """(16 n_terms, 128) f32: term t of the step's rows summed by key,
        key hi * 16 + lo of this tile at [16 t + lo, hi % 128]."""

        def group(g, part):
            keys = keys_ref[rows(g), :]
            hi = (keys >> _LO_BITS) - j * _LANES  # arithmetic: key -1 has hi -1
            lo = keys & (_LO - 1)
            terms = terms_of(g)
            hi_ids = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
            lo_ids = jax.lax.broadcasted_iota(jnp.int32, (_LO, _LANES), 0)
            for s in range(_SUBLANES):  # 128 rows a matmul, rows on lanes
                h = jnp.where(hi_ids == hi[s : s + 1, :], 1.0, 0.0).astype(jnp.bfloat16)
                on_lo = lo_ids == lo[s : s + 1, :]
                lhs = jnp.concatenate([jnp.where(on_lo, t[s : s + 1, :], 0.0) for t in terms])
                part = part + jax.lax.dot_general(
                    lhs.astype(jnp.bfloat16), h, _ROWS_NT, preferred_element_type=jnp.float32
                )
            return part

        init = jnp.zeros((_LO * n_terms, _LANES), jnp.float32)
        return jax.lax.fori_loop(0, n_groups, group, init)

    def value_terms(g):
        out = []
        for kind, v_ref in zip(kinds, vals_refs):
            out += _mxu_terms(kind, v_ref[rows(g), :])
        if with_presence:
            out.append(jnp.ones((_SUBLANES, _LANES), jnp.float32))
        return out

    n_terms = sum(_MXU_TERMS[k] for k in kinds) + with_presence
    part = contract(value_terms, n_terms)
    t = 0
    for a, kind in enumerate(kinds):
        ps = [part[_LO * (t + b) : _LO * (t + b + 1)] for b in range(_MXU_TERMS[kind])]
        t += len(ps)
        o_ref = out_refs[a]
        if kind == "int32":
            # exact per-step byte counts; int32 shifts and adds wrap as the
            # VPU path's int32 adds do
            o_ref[...] += functools.reduce(
                jnp.add, [p.astype(jnp.int32) << (8 * b) for b, p in enumerate(ps)]
            )
            continue
        x = functools.reduce(jnp.add, ps[::-1])  # smallest term first
        y = x - comp[a][...]  # Kahan: the steps' partials fold in compensated
        acc = o_ref[...]
        total = acc + y
        comp[a][...] = (total - acc) - y
        o_ref[...] = total
    if with_presence:
        out_refs[n_vals][...] += part[_LO * t :].astype(jnp.int32)

    if floats:

        def bad_group(g, bad):
            live = keys_ref[rows(g), :] >= 0
            for a in floats:
                odd = live & ~(jnp.abs(vals_refs[a][rows(g), :]) < jnp.inf)
                bad = bad | jnp.where(odd, 1, 0)
            return bad

        bad = jax.lax.fori_loop(0, n_groups, bad_group, jnp.zeros((_SUBLANES, _LANES), jnp.int32))

        @pl.when(jnp.max(bad) > 0)
        def _count_nonfinite():
            def flag_terms(g):
                out = []
                for a in floats:
                    v = vals_refs[a][rows(g), :]
                    odd = ~(jnp.abs(v) < jnp.inf)
                    out.append(jnp.where(odd & (v != -jnp.inf), 1.0, 0.0))
                    out.append(jnp.where(odd & (v != jnp.inf), 1.0, 0.0))
                return out

            counts = contract(flag_terms, 2 * len(floats))
            for f, a in enumerate(floats):
                nonfinite[a][...] += counts[2 * _LO * f : 2 * _LO * (f + 1)]

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        for a in floats:
            pos = nonfinite[a][:_LO] > 0
            neg = nonfinite[a][_LO:] > 0
            total = out_refs[a][...] - comp[a][...]
            out_refs[a][...] = jnp.where(
                pos, jnp.where(neg, jnp.nan, jnp.inf), jnp.where(neg, -jnp.inf, total)
            )


def fused_segreduce_pallas(
    keys: jnp.ndarray,
    values: Sequence[jnp.ndarray],
    ops: Sequence[str],
    num_keys: int,
    mask: Optional[jnp.ndarray] = None,
    with_presence: bool = True,
    tile: int = 1024,
    interpret: bool = True,
) -> Tuple[Tuple[jnp.ndarray, ...], Optional[jnp.ndarray]]:
    """Fused multi-aggregate segmented reduction in ONE pallas_call.

    ``values[i]`` is aggregated under ``ops[i]`` into its own (num_keys,)
    accumulator (input dtypes preserved); rows with ``mask == False`` (and
    padding) contribute each op's identity.  Returns ``(accs, presence)``
    where ``presence[k]`` counts unmasked rows of segment k (None when
    ``with_presence=False``).  ``tile`` is the number of rows one grid step
    streams (rounded to whole (8, 128) groups)."""
    n_aggs = len(values)
    if n_aggs != len(ops):
        raise ValueError(f"{n_aggs} value columns but {len(ops)} ops")
    for op in ops:
        if op not in OPS:
            raise ValueError(f"unknown segreduce op {op!r}")
    dts = [acc_dtype(v.dtype) for v in values]
    n = int(keys.shape[0])
    if n == 0:
        accs = tuple(
            jnp.full((num_keys,), op_identity(op, dt), dt).astype(v.dtype)
            for op, dt, v in zip(ops, dts, values)
        )
        pres = jnp.zeros((num_keys,), jnp.int32) if with_presence else None
        return accs, pres
    path = kernel_path(ops, [v.dtype for v in values], num_keys)
    if path == "mxu":
        tile = min(tile, _MXU_STEP_ROWS)
    group = _SUBLANES * _LANES
    n_rows = -(-n // group) * _SUBLANES  # lane rows, whole (8, 128) groups
    block_rows = min(max(_SUBLANES, tile // group * _SUBLANES), n_rows)
    n_rows += (-n_rows) % block_rows
    pad_n = n_rows * _LANES - n

    def lane_dense(x, fill):
        if pad_n:
            x = jnp.pad(x, (0, pad_n), constant_values=fill)
        return x.reshape(n_rows, _LANES)

    keys = keys.astype(jnp.int32)
    if mask is not None:
        keys = jnp.where(mask.astype(bool), keys, -1)  # -1 matches no key id
    keys_2d = lane_dense(keys, -1)  # padding rows are masked rows
    vals_2d = [lane_dense(v.astype(dt), 0) for v, dt in zip(values, dts)]
    row_block = pl.BlockSpec((block_rows, _LANES), lambda j, i: (i, 0))
    n_blocks = n_rows // block_rows
    if path == "mxu":
        return _mxu_segreduce(
            keys_2d, vals_2d, values, num_keys, with_presence, row_block, n_blocks, interpret
        )
    n_tiles = -(-num_keys // KEY_TILE)
    out_rows = -(-n_tiles // _TILES_PER_OUT) * _SUBLANES
    acc_ops = tuple(ops) + (("sum",) if with_presence else ())
    acc_dts = dts + ([jnp.dtype(jnp.int32)] if with_presence else [])
    # one (8, 128) output block holds the keys of _TILES_PER_OUT key tiles,
    # so it stays resident while they run and is written back once
    out_block = pl.BlockSpec((_SUBLANES, _LANES), lambda j, i: (j // _TILES_PER_OUT, 0))
    outs = pl.pallas_call(
        functools.partial(
            _fused_kernel, ops=acc_ops, n_vals=n_aggs, n_groups=block_rows // _SUBLANES
        ),
        grid=(n_tiles, n_blocks),
        in_specs=[row_block] * (1 + n_aggs),
        out_specs=tuple(out_block for _ in acc_dts),
        out_shape=tuple(jax.ShapeDtypeStruct((out_rows, _LANES), dt) for dt in acc_dts),
        scratch_shapes=[pltpu.VMEM((KEY_TILE, _LANES), dt) for dt in acc_dts],
        interpret=interpret,
        name="segreduce",
    )(keys_2d, *vals_2d)
    accs = tuple(o.reshape(-1)[:num_keys].astype(v.dtype) for o, v in zip(outs, values))
    pres = outs[n_aggs].reshape(-1)[:num_keys] if with_presence else None
    return accs, pres


def _mxu_segreduce(keys_2d, vals_2d, values, num_keys, with_presence, row_block, n_blocks,
                   interpret):
    kinds = tuple(jnp.dtype(v.dtype).name for v in values)
    n_tiles = -(-num_keys // MXU_KEYS)
    acc_dts = [jnp.int32 if k == "int32" else jnp.float32 for k in kinds]
    acc_dts += [jnp.int32] if with_presence else []
    n_floats = sum(k != "int32" for k in kinds)
    out_block = pl.BlockSpec((_LO, _LANES), lambda j, i: (0, j))
    outs = pl.pallas_call(
        functools.partial(
            _mxu_kernel, kinds=kinds, with_presence=with_presence,
            n_groups=row_block.block_shape[0] // _SUBLANES,
        ),
        grid=(n_tiles, n_blocks),
        in_specs=[row_block] * (1 + len(values)),
        out_specs=tuple(out_block for _ in acc_dts),
        out_shape=tuple(
            jax.ShapeDtypeStruct((_LO, n_tiles * _LANES), dt) for dt in acc_dts
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32)
            for _ in range(n_floats) for rows in (_LO, 2 * _LO)
        ],
        interpret=interpret,
        name="segreduce",
    )(keys_2d, *vals_2d)
    # [lo, hi] -> key hi * 16 + lo
    flat = [o.T.reshape(-1)[:num_keys] for o in outs]
    accs = tuple(f.astype(v.dtype) for f, v in zip(flat, values))
    return accs, (flat[len(values)] if with_presence else None)


def segreduce_pallas(
    keys: jnp.ndarray,
    values: jnp.ndarray,
    num_keys: int,
    op: str = "sum",
    tile: int = 1024,
    interpret: bool = True,
) -> jnp.ndarray:
    """Single-op segmented reduction (the fused kernel with one aggregate).
    Input dtype is preserved; empty segments hold the op's identity."""
    (acc,), _ = fused_segreduce_pallas(
        keys, (values,), (op,), num_keys,
        mask=None, with_presence=False, tile=tile, interpret=interpret,
    )
    return acc
