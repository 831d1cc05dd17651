# Pallas TPU kernels: segmented (group-by) aggregation, single-op and
# fused multi-aggregate.
#
# TPU adaptation of the paper's hash-table index-set materialization
# (Fig. 1 bottom): scalar hashing is hostile to the VPU, so each key id owns
# a row of a VMEM-resident accumulator block and every input row is compared
# against a whole tile of key ids at once.  A hit selects the row's value,
# a miss the op's identity, and the accumulator folds it in with the op
# itself (add / max / min) — one masked VPU reduction for every op and dtype,
# so integer sums (SQL COUNT is an int32 sum of ones) stay exact and never
# need an integer matmul, which the MXU does not offer.
#
# The fused kernel evaluates a whole query's aggregate group in ONE
# pallas_call: per row it builds the key-tile hit mask once and drives every
# aggregate's accumulator plus the group-presence histogram from it.  The
# filter mask is folded into the keys before the call (a masked row gets key
# -1, which matches no key id), so masked and padded rows contribute each
# op's identity by construction.
#
# Layout: every column is viewed lane-dense as (rows, 128) — one input block
# is (block_rows, 128), so HBM and VMEM hold the data at its own size (an
# (N, 1) column would take a whole 128-lane row per element).  Key ids run
# along sublanes: a VMEM accumulator is (KEY_TILE, 128), one key id per row
# and one lane partial per lane.  The grid is (key tiles, row blocks): the
# row axis is the reduction, so each key tile's accumulators stay resident
# while every row block streams past them (TPU grids run sequentially, so
# the read-modify-write accumulation is race-free).  After the last row
# block the kernel folds the lane partials and writes the tile's keys along
# the lanes of a dense (keys / 128, 128) output, 4 bytes a key.  The work is
# rows x key tiles: the planner prices it so (planner/cost.py).  Integer and
# float accumulators are preserved; sub-f32 floats accumulate in f32 and are
# cast back.
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
    n_tiles = -(-num_keys // KEY_TILE)
    out_rows = -(-n_tiles // _TILES_PER_OUT) * _SUBLANES
    acc_ops = tuple(ops) + (("sum",) if with_presence else ())
    acc_dts = dts + ([jnp.dtype(jnp.int32)] if with_presence else [])
    row_block = pl.BlockSpec((block_rows, _LANES), lambda j, i: (i, 0))
    # one (8, 128) output block holds the keys of _TILES_PER_OUT key tiles,
    # so it stays resident while they run and is written back once
    out_block = pl.BlockSpec((_SUBLANES, _LANES), lambda j, i: (j // _TILES_PER_OUT, 0))
    outs = pl.pallas_call(
        functools.partial(
            _fused_kernel, ops=acc_ops, n_vals=n_aggs, n_groups=block_rows // _SUBLANES
        ),
        grid=(n_tiles, n_rows // block_rows),
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
