# Vectorized JAX executor backend: pattern-directed lowering of forelem
# programs to jitted JAX with selectable index-set materialization methods
# (the Fig. 1 'nested loop' vs 'hash table' choice becomes
# scan/sort/one-hot-MXU/Pallas-kernel) and selectable parallel execution
# (vmap emulation or shard_map over a mesh axis with psum/all_to_all — the
# generated-MPI-code analogue).
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.ir import (
    ArrayRead,
    BinOp,
    Const,
    Expr,
    FieldRef,
    Program,
    Var,
    apply_order_limit,
)
from repro.data.multiset import Database, DictColumn, Multiset

from repro.kernels.segreduce import ops as segops

from .codegen import (
    FUSABLE_AGG_OPS,
    DistinctReadSpec,
    JoinSpec,
    UnsupportedProgram,
    _densify,
    _jnp_binop,
    _op_identity,
    cols_len_shape,
    extract_spec,
    fused_agg_groups,
    required_columns,
)
from .interface import register_backend
from .resident import Key, ResidentColumns

# engine accumulate-op spelling -> segreduce kernel spelling
_KERNEL_OPS = {"+": "sum", "max": "max", "min": "min"}
# engine accumulate-op spelling -> the jax.lax collective that combines a
# shard_map aggregate's per-device partials
_COMBINE = {"+": "psum", "max": "pmax", "min": "pmin"}


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` renamed, so ``jax.jit`` calls its module ``jit_<name>`` and a
    device trace can tell the engine's programs apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def program_name(spec) -> str:
    """The monolithic program's name, from the plan's operators: ``q_``
    then ``join``, ``groupby``, ``scalar`` and ``project``, those it has,
    in that order (``q_groupby``, ``q_join_groupby``, ``q_scalar``)."""
    parts = [
        op
        for op, has in (
            ("join", spec.joins),
            ("groupby", spec.aggs or spec.distinct_reads or any(j.aggs for j in spec.joins)),
            ("scalar", spec.scalar_reduces),
            ("project", spec.filter_projects),
        )
        if has
    ]
    return "q_" + "_".join(parts or ["empty"])


@dataclass
class CodegenChoices:
    """The Fig. 1 decision: how index sets are materialized and how foralls
    execute.

    agg_method: 'dense'   — scatter-add into a dense accumulator (requires
                             dictionary-encoded integer keys; the TPU
                             analogue of the paper's hash table),
                'onehot'  — one-hot × MXU matmul histogram,
                'sort'    — sort + segment reduction (tree-index analogue),
                'kernel'  — Pallas segreduce kernel (VMEM-resident
                             accumulator; Mosaic-compiled on TPU, the jnp
                             fused fallback elsewhere).
    parallel:   'none'    — single-program,
                'vmap'    — N-way partitioned execution emulated with vmap
                             (semantics of the forall on one device),
                'shard_map' — SPMD over a real mesh axis (psum combine);
                              the generated-MPI-code analogue.
    join_method: 'auto'   — unique-lookup when the build key is unique on
                             the actual data, expansion otherwise,
                'lookup'  — one match per probe row (requires a
                             key-unique build side),
                'expand'  — each probe's match run, gather-expanded to
                             max key multiplicity (general duplicate-key
                             equi-join).
                 Either probes a dense unique integer build key through a
                 position table, any other by binary search (join_probe).
    """

    agg_method: str = "dense"
    parallel: str = "none"
    mesh: Optional[jax.sharding.Mesh] = None
    axis_name: str = "data"
    donate: bool = False
    join_method: str = "auto"


class JaxLowering:
    """Compile a forelem Program into a callable over jnp column arrays."""

    def __init__(
        self,
        program: Program,
        db: Database,
        choices: Optional[CodegenChoices] = None,
        chunked: bool = False,
    ):
        """``chunked``: a chunked executor runs the joins through
        ``_join_rows`` over each partition's sorted build side, not
        ``build()``, so every probe binary-searches."""
        self.program = program
        self.db = db
        self.choices = choices or CodegenChoices()
        self.spec = extract_spec(program)
        # Max build-side key multiplicity per join, from the actual data at
        # compile time.  It sizes the static gather-expansion (probe_rows ×
        # M output slots); M == 1 degenerates to the unique-lookup plan and
        # M == 0 marks an empty build side (all probes miss).
        self.join_multiplicity: List[int] = []
        # Per join, the build key's (kmin, kmax) when the monolithic probe
        # of a unique key goes through a position table over that domain,
        # else None (binary search of the sorted build side); join_probe
        # names the choice.  Read from the same data as M, under the same
        # recompile rule.
        self.join_key_range: List[Optional[Tuple[int, int]]] = []
        for j in self.spec.joins:
            key_range = None
            if j.build_table in db and len(db[j.build_table]):
                bk = np.asarray(db[j.build_table].field(j.build_key))
                uniq, counts = np.unique(bk, return_counts=True)
                mult = int(counts.max()) if len(counts) else 0
                if mult == 1 and not chunked:
                    key_range = self._dense_key_range(j, uniq)
            else:
                mult = 0 if j.build_table in db else 1
            self.join_key_range.append(key_range)
            if self.choices.join_method == "lookup" and mult > 1:
                raise UnsupportedProgram(
                    f"join_method='lookup' but build side {j.build_table}.{j.build_key} "
                    "has duplicate keys — use 'expand' or 'auto'"
                )
            self.join_multiplicity.append(mult)
        self.join_probe: List[str] = [
            "search" if r is None else "direct" for r in self.join_key_range
        ]
        # The path ('mxu' or 'vpu', kernels/segreduce.kernel_path) of each
        # segreduce kernel launch of the whole-table program, recorded while
        # ``build()``'s program is traced; none under the jnp fallback, and
        # none for the chunk programs of the partitioned backend.
        self.segreduce_path: List[str] = []
        self._launches: Optional[List[str]] = None
        # key-space sizes for dense accumulators (dictionary-encoded columns)
        self.num_keys: Dict[Tuple[str, str], int] = {}
        for agg in self.spec.aggs:
            self.num_keys[(agg.table, agg.key_field)] = self._key_space(agg.table, agg.key_field)
        for dr in self.spec.distinct_reads:
            self.num_keys[(dr.table, dr.field)] = self._key_space(dr.table, dr.field)
        for j in self.spec.joins:
            for ja in j.aggs:
                self.num_keys[(ja.key.table, ja.key.field)] = self._key_space(
                    ja.key.table, ja.key.field
                )
        # Fused-kernel groups: aggregates one fused pallas_call evaluates
        # together under agg_method='kernel' (same table / GROUP-BY key /
        # row predicate, so they share one hit matrix and presence pass).
        self.fused_groups: List[List[int]] = (
            fused_agg_groups(self.spec.aggs) if self.choices.agg_method == "kernel" else []
        )
        # Loud method fallbacks: when a requested agg_method cannot evaluate
        # an op, _aggregate downgrades that aggregate to 'dense' — the notes
        # here are surfaced by the optimizer into the trace and the
        # decision's rejections so the downgrade is never silent.
        self.method_notes: List[str] = []
        # shard_map only: the row count of each table Plan places on the
        # mesh as row shards, padded to a multiple of the mesh size (rows
        # past the count add each op's identity), and the combine each run
        # issues per accumulator: each aggregate's value, then each key's
        # presence count.
        self.mesh_rows: Dict[str, int] = self._mesh_rows()
        mesh_aggs = [a for a in self.spec.aggs if a.table in self.mesh_rows]
        self.collectives: List[str] = [_COMBINE[a.op] for a in mesh_aggs] + [
            _COMBINE["+"] for _ in {(a.table, a.key_field) for a in mesh_aggs}
        ]
        if self.choices.agg_method in ("onehot", "kernel"):
            supported = ("+",) if self.choices.agg_method == "onehot" else FUSABLE_AGG_OPS
            labelled = [
                (f"agg {a.array}[{a.table}.{a.key_field}]", a.op) for a in self.spec.aggs
            ] + [
                (f"join-agg {ja.array}[{ja.key.table}.{ja.key.field}]", ja.op)
                for j in self.spec.joins
                for ja in j.aggs
            ]
            for label, op in labelled:
                if op not in supported:
                    self.method_notes.append(
                        f"{label}: op {op!r} unsupported by "
                        f"agg_method={self.choices.agg_method!r} — "
                        "this aggregate falls back to 'dense'"
                    )

    def _mesh_rows(self) -> Dict[str, int]:
        """Tables the shard_map path splits over the mesh: those the program
        reads through its aggregates alone (a join, scalar reduction,
        projection, membership set or key-column distinct read needs every
        row, unpadded), with their row counts."""
        c, spec = self.choices, self.spec
        if c.parallel != "shard_map" or spec.n_parts <= 1:
            return {}
        if c.mesh is None:
            raise UnsupportedProgram("shard_map parallel requires a mesh")
        keyed = {(a.table, a.key_field) for a in spec.aggs}
        whole = {t for j in spec.joins for t in (j.probe_table, j.build_table)}
        whole |= {sr.table for sr in spec.scalar_reduces}
        whole |= {fp.table for fp in spec.filter_projects}
        whole |= {a.member_filter[1] for a in spec.aggs if a.member_filter is not None}
        whole |= {dr.table for dr in spec.distinct_reads if (dr.table, dr.field) not in keyed}
        tables = {a.table for a in spec.aggs} - whole
        bad = sorted({a.op for a in spec.aggs if a.table in tables} - set(_COMBINE))
        if bad:
            raise UnsupportedProgram(f"shard_map cannot combine op(s) {bad}")
        return {t: len(self.db[t]) for t in tables}

    def _key_space(self, table: str, fld: str) -> int:
        col = self.db[table].columns[fld]
        if isinstance(col, DictColumn):
            return col.num_keys
        vals = np.asarray(col.materialize())
        if vals.dtype == object:
            raise UnsupportedProgram(
                f"column {table}.{fld} holds strings — apply data reformatting "
                "(dictionary encoding) before JAX lowering, or use the "
                "reference/numpy backends"
            )
        if not np.issubdtype(vals.dtype, np.integer):
            raise UnsupportedProgram(f"non-integer key column {table}.{fld}")
        return int(vals.max()) + 1 if len(vals) else 1

    def _dense_key_range(self, j: JoinSpec, uniq: np.ndarray) -> Optional[Tuple[int, int]]:
        """``(kmin, kmax)`` of a unique build key a position table can
        probe: both join keys integers, the domain within int32 and at most
        4x the build rows (the table at most 4x the key column's bytes), and
        the table no larger than the sorted keys, order and run bounds the
        binary search would hold instead (2 x (build + probe rows) int32)."""
        probe_dtype = np.asarray(self.db[j.probe_table].field(j.probe_fk)).dtype
        if not (np.issubdtype(uniq.dtype, np.integer) and np.issubdtype(probe_dtype, np.integer)):
            return None
        kmin, kmax = int(uniq[0]), int(uniq[-1])
        i32 = np.iinfo(np.int32)
        n_build, n_probe = len(uniq), len(self.db[j.probe_table])
        slots = min(4 * n_build, 2 * (n_build + n_probe), i32.max)
        if kmin < i32.min or kmax > i32.max or kmax - kmin + 1 > slots:
            return None
        return kmin, kmax

    # -- expression → jnp ------------------------------------------------------
    def _vec(self, e: Expr, cols: Dict[str, Dict[str, jnp.ndarray]], table: str, arrays: Dict[str, jnp.ndarray]):
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        if isinstance(e, Var):
            params = cols.get("__params__", {})
            if e.name in params:
                return params[e.name]
            raise UnsupportedProgram(f"free Var {e.name} in vectorized expr")
        if isinstance(e, FieldRef):
            return cols[e.table][e.field]
        if isinstance(e, ArrayRead):
            key = self._vec(e.key, cols, table, arrays)
            return arrays[e.array][key]
        if isinstance(e, BinOp):
            l = self._vec(e.lhs, cols, table, arrays)
            r = self._vec(e.rhs, cols, table, arrays)
            return _jnp_binop(e.op, l, r)
        raise UnsupportedProgram(f"cannot vectorize {e!r}")

    def _pred_mask(self, pred: Optional[Expr], cols, table) -> Optional[jnp.ndarray]:
        if pred is None:
            return None
        # predicates use loopvar '_'
        return self._vec(pred, cols, table, {})

    # -- aggregation kernels ----------------------------------------------------
    def _aggregate(self, keys, values, num_keys: int, op: str):
        method = self.choices.agg_method
        # Per-op downgrades are recorded in self.method_notes (built at
        # lowering time) and surfaced by the optimizer — not silent.
        if op != "+" and method == "onehot":
            method = "dense"
        if op not in FUSABLE_AGG_OPS and method == "kernel":
            method = "dense"
        if method == "dense":
            if op == "+":
                return jax.ops.segment_sum(values, keys, num_segments=num_keys)
            if op == "max":
                return jax.ops.segment_max(values, keys, num_segments=num_keys)
            if op == "min":
                return jax.ops.segment_min(values, keys, num_segments=num_keys)
            raise UnsupportedProgram(op)
        if method == "onehot":
            oh = jax.nn.one_hot(keys, num_keys, dtype=values.dtype)
            return oh.T @ values
        if method == "sort":
            order = jnp.argsort(keys)
            sk, sv = keys[order], values[order]
            if op == "+":
                return jax.ops.segment_sum(sv, sk, num_segments=num_keys, indices_are_sorted=True)
            if op == "max":
                return jax.ops.segment_max(sv, sk, num_segments=num_keys, indices_are_sorted=True)
            if op == "min":
                return jax.ops.segment_min(sv, sk, num_segments=num_keys, indices_are_sorted=True)
            raise UnsupportedProgram(op)
        if method == "kernel":
            self._note_launch((_KERNEL_OPS[op],), (values,), num_keys)
            return segops.segreduce(keys, values, num_keys, op=_KERNEL_OPS[op])
        raise ValueError(f"bad agg method {method}")

    def _note_launch(self, ops, values, num_keys: int) -> None:
        if self._launches is not None:
            path = segops.launch_path(ops, [v.dtype for v in values], num_keys)
            if path is not None:
                self._launches.append(path)

    # -- shared per-op input preparation ----------------------------------------
    #
    # These encapsulate the masking subtleties fixed in PR 2 (masked/padded
    # rows must contribute the op *identity*, funneled to key 0) so every
    # backend that evaluates an aggregation — monolithic or per-chunk
    # (backends/partitioned.py) — goes through one implementation.

    def _agg_value(self, value: Expr, keys, cols, table: str, arrays):
        if isinstance(value, Const):
            return jnp.full(
                keys.shape, value.value,
                dtype=jnp.int32 if isinstance(value.value, int) else jnp.float32,
            )
        return jnp.broadcast_to(self._vec(value, cols, table, arrays), keys.shape)

    def agg_inputs(self, agg, cols, arrays):
        """(keys, values, presence-ones, mask) for one AggSpec over ``cols``
        (which may be a chunk's column view)."""
        keys = cols[agg.table][agg.key_field]
        values = self._agg_value(agg.value, keys, cols, agg.table, arrays)
        mask = self._pred_mask(agg.filter_pred, cols, agg.table)
        if agg.member_filter is not None:
            mf, mt, mfld = agg.member_filter
            member = jnp.isin(cols[agg.table][mf], cols[mt][mfld])
            mask = member if mask is None else (mask & member)
        if mask is not None:
            # masked-out rows must contribute the op's *identity* —
            # funneling them into segment 0 with value 0 corrupts that
            # segment's max/min whenever its true extremum is beyond 0
            values = jnp.where(mask, values, _op_identity(agg.op, values.dtype))
            keys = jnp.where(mask, keys, 0)
        ones = jnp.ones(keys.shape, jnp.int32)
        if mask is not None:
            ones = jnp.where(mask, ones, 0)
        return keys, values, ones, mask

    def fused_agg_inputs(self, aggs, cols, arrays):
        """(keys, value-column tuple, combined row mask) for a fused
        aggregate group (one entry of ``self.fused_groups``).  Unlike
        ``agg_inputs`` the mask is NOT pre-applied: the fused kernel
        evaluates it in-pass, funneling masked rows to each op's identity
        via the shared hit matrix."""
        first = aggs[0]
        keys = cols[first.table][first.key_field]
        mask = self._pred_mask(first.filter_pred, cols, first.table)
        if first.member_filter is not None:
            mf, mt, mfld = first.member_filter
            member = jnp.isin(cols[first.table][mf], cols[mt][mfld])
            mask = member if mask is None else (mask & member)
        values = tuple(self._agg_value(a.value, keys, cols, a.table, arrays) for a in aggs)
        return keys, values, mask

    def join_agg_inputs(self, ja, j: JoinSpec, jr: "_JoinRows", cols):
        """(keys, values, presence-ones) for one JoinAgg over the joined
        row pairs ``jr`` (absent slots contribute the op identity)."""
        keys = self._join_gather(ja.key, j, jr, cols)
        if isinstance(ja.value, Const):
            values = jnp.full(
                keys.shape, ja.value.value,
                dtype=jnp.int32 if isinstance(ja.value.value, int) else jnp.float32,
            )
        else:
            values = jnp.broadcast_to(self._join_gather(ja.value, j, jr, cols), keys.shape)
        values = jnp.where(jr.present, values, _op_identity(ja.op, values.dtype))
        keys = jnp.where(jr.present, keys, 0)
        ones = jnp.where(jr.present, 1, 0).astype(jnp.int32)
        return keys, values, ones

    # -- per-chunk kernel entry points (bucketed jit) ---------------------------
    #
    # The partitioned backend (backends/partitioned.py) pads each chunk's
    # row count up to a small geometric set of shape buckets and wraps
    # these functions in ``jax.jit``: shapes are static per bucket, so one
    # XLA compilation serves every chunk that lands in the same bucket.
    # Rows at index >= ``n_valid`` are padding; they contribute the
    # accumulate op's *identity* (the PR-2 masking discipline) so they can
    # never perturb a segment, and padded join/projection slots carry
    # present=False.

    def chunk_agg_fn(self, agg, with_presence: bool = True) -> Callable:
        """(padded chunk cols, n_valid, env, arrays) -> (partial acc,
        presence partial or None).

        ``with_presence=False`` skips the presence histogram scatter — the
        partitioned runner passes it when the presence of an *unfiltered*
        aggregation is already memoized from a previous run (it is a pure
        function of the key column, roughly half the kernel's scatter
        work)."""
        nk = self.num_keys[(agg.table, agg.key_field)]

        def fn(chunk_cols, n_valid, env, arrays):
            cols = dict(env)
            cols[agg.table] = chunk_cols
            keys, values, ones, _ = self.agg_inputs(agg, cols, arrays)
            valid = jnp.arange(keys.shape[0], dtype=jnp.int32) < n_valid
            keys = jnp.where(valid, keys, 0)
            values = jnp.where(valid, values, _op_identity(agg.op, values.dtype))
            acc = self._aggregate(keys, values, nk, agg.op)
            if not with_presence:
                return acc, None
            ones = jnp.where(valid, ones, 0)
            return acc, self._aggregate(keys, ones, nk, "+")

        return _named(fn, "chunk_agg")

    def chunk_fused_agg_fn(self, aggs, with_presence: bool = True) -> Callable:
        """(padded chunk cols, n_valid, env, arrays) -> (tuple of partial
        accumulators — one per aggregate in the group, input dtypes
        preserved — and the presence partial or None).

        The fused variant of ``chunk_agg_fn``: the whole aggregate group
        runs in ONE fused segreduce launch per chunk (filter mask, padding
        mask and every accumulator in a single data pass); the partitioned
        runner partial-merges the multi-accumulator state across chunks
        element-wise under each aggregate's own op."""
        first = aggs[0]
        nk = self.num_keys[(first.table, first.key_field)]
        ops = tuple(_KERNEL_OPS[a.op] for a in aggs)

        def fn(chunk_cols, n_valid, env, arrays):
            cols = dict(env)
            cols[first.table] = chunk_cols
            keys, values, mask = self.fused_agg_inputs(aggs, cols, arrays)
            valid = jnp.arange(keys.shape[0], dtype=jnp.int32) < n_valid
            mask = valid if mask is None else (mask & valid)
            return segops.fused_segreduce(
                keys, values, ops, nk, mask=mask, with_presence=with_presence
            )

        return _named(fn, "chunk_fused_agg")

    def chunk_reduce_fn(self, sr) -> Callable:
        """(padded chunk cols, n_valid, env, arrays) -> partial scalar sum."""

        def fn(chunk_cols, n_valid, env, arrays):
            cols = dict(env)
            cols[sr.table] = chunk_cols
            m = cols_len_shape(cols, sr.table)[0]
            expr = self._vec(sr.expr, cols, sr.table, arrays)
            mask = jnp.arange(m, dtype=jnp.int32) < n_valid
            if sr.match_field is not None:
                mv = sr.match_value
                if isinstance(mv, Const):
                    mval = jnp.asarray(mv.value)
                else:
                    mval = cols["__params__"][mv.name]
                mask = mask & (cols[sr.table][sr.match_field] == mval)
            pmask = self._pred_mask(sr.filter_pred, cols, sr.table)
            if pmask is not None:
                mask = mask & pmask
            vals = jnp.broadcast_to(expr, (m,))
            return jnp.sum(jnp.where(mask, vals, 0))

        return _named(fn, "chunk_reduce")

    def chunk_project_fn(self, fp) -> Callable:
        """(padded chunk cols, n_valid, env) -> (item columns, present mask)."""

        def fn(chunk_cols, n_valid, env):
            cols = dict(env)
            cols[fp.table] = chunk_cols
            m = cols_len_shape(cols, fp.table)[0]
            mask = self._pred_mask(fp.filter_pred, cols, fp.table)
            valid = jnp.arange(m, dtype=jnp.int32) < n_valid
            mask = valid if mask is None else (mask & valid)
            items = tuple(
                jnp.broadcast_to(self._vec(el, cols, fp.table, {}), (m,)) for el in fp.items
            )
            return items, mask

        return _named(fn, "chunk_project")

    def chunk_join_fn(self, j: JoinSpec, mult: int, with_presence: bool = True) -> Callable:
        """(padded probe cols, n_valid_probe, sorted+padded build cols,
        sorted build keys, n_valid_build, env) -> join-agg partials (one
        (acc, presence-or-None) pair per JoinAgg), or (item columns,
        present, probe_idx) for a materialized join.

        The build side arrives already gathered into sorted-key order (the
        host sorts once per partition), so the in-kernel ``order`` mapping
        is the identity.  ``with_presence=False`` skips the group-presence
        scatters (memoized across runs for filter-free joins, exactly like
        the single-table aggregation presence)."""

        def fn(probe_cols, n_valid_probe, build_cols, sorted_keys, n_valid_build, env):
            cols = dict(env)
            cols[j.probe_table] = probe_cols
            cols[j.build_table] = build_cols
            ident = jnp.arange(sorted_keys.shape[0], dtype=jnp.int32)
            jr = self._join_rows(
                j, mult, cols, build_sorted=(ident, sorted_keys), n_valid_build=n_valid_build
            )
            n = cols_len_shape(cols, j.probe_table)[0]
            valid = jnp.arange(n, dtype=jnp.int32) < n_valid_probe
            jr.present = jr.present & (valid if jr.probe_idx is None else valid[jr.probe_idx])
            if j.aggs:
                outs = []
                for ja in j.aggs:
                    nk = self.num_keys[(ja.key.table, ja.key.field)]
                    keys, values, ones = self.join_agg_inputs(ja, j, jr, cols)
                    outs.append(
                        (
                            self._aggregate(keys, values, nk, ja.op),
                            self._aggregate(keys, ones, nk, "+") if with_presence else None,
                        )
                    )
                return tuple(outs)
            items = tuple(self._join_gather(el, j, jr, cols) for el in j.items)
            return items, jr.present, jr.probe_idx

        return _named(fn, "chunk_join")

    # -- build the callable -------------------------------------------------------
    def build(self) -> Callable[[Dict[str, Dict[str, jnp.ndarray]]], Dict[str, Any]]:
        spec = self.spec

        def run(cols: Dict[str, Dict[str, jnp.ndarray]]) -> Dict[str, Any]:
            self._launches = []
            arrays: Dict[str, jnp.ndarray] = {}
            presence: Dict[Tuple[str, str], jnp.ndarray] = {}
            out: Dict[str, Any] = {}

            # --- aggregations ------------------------------------------------
            # Under agg_method='kernel' (sequential), each fused group runs
            # as ONE fused segreduce launch — mask, every accumulator and
            # the presence histogram in a single data pass — at the position
            # of its first member; everything else keeps the per-aggregate
            # path (vmap/shard_map partials merge per-op downstream).
            fused_at: Dict[int, List[int]] = {}
            if self.fused_groups and self.choices.parallel == "none":
                fused_at = {g[0]: g for g in self.fused_groups}
            fused_members = {i for g in fused_at.values() for i in g}
            # a key's presence is the last one written: only its writer
            # computes it (one presence combine per key on the mesh)
            writer = {
                (a.table, a.key_field): ai
                for ai, a in enumerate(spec.aggs)
                if ai in fused_at or ai not in fused_members
            }
            for ai, agg in enumerate(spec.aggs):
                nk = self.num_keys[(agg.table, agg.key_field)]
                group = fused_at.get(ai)
                if group is not None:
                    gaggs = [spec.aggs[i] for i in group]
                    keys, values, mask = self.fused_agg_inputs(gaggs, cols, arrays)
                    ops = tuple(_KERNEL_OPS[a.op] for a in gaggs)
                    self._note_launch(ops, values, nk)
                    accs, pres = segops.fused_segreduce(keys, values, ops, nk, mask=mask)
                    for a, acc in zip(gaggs, accs):
                        arrays[a.array] = acc
                    presence[(agg.table, agg.key_field)] = pres
                    continue
                if ai in fused_members:
                    continue  # evaluated with its group above
                safe_keys, values, ones, _ = self.agg_inputs(agg, cols, arrays)
                n_valid = self.mesh_rows.get(agg.table)
                arrays[agg.array] = self._parallel_aggregate(safe_keys, values, nk, agg.op, n_valid)
                if writer[(agg.table, agg.key_field)] == ai:
                    presence[(agg.table, agg.key_field)] = self._parallel_aggregate(
                        safe_keys, ones, nk, "+", n_valid
                    )

            # --- joins (unique-lookup or duplicate-key expansion) -------------
            # Before distinct reads: join-aggregates fill `arrays`/`presence`
            # that the guarded distinct-read result loops consume.
            for j, mult, key_range in zip(
                spec.joins, self.join_multiplicity, self.join_key_range
            ):
                jr = self._join_rows(j, mult, cols, key_range=key_range)
                if j.aggs:
                    for ja in j.aggs:
                        nk = self.num_keys[(ja.key.table, ja.key.field)]
                        safe_keys, values, ones = self.join_agg_inputs(ja, j, jr, cols)
                        arrays[ja.array] = self._aggregate(safe_keys, values, nk, ja.op)
                        presence[(ja.key.table, ja.key.field)] = self._aggregate(
                            safe_keys, ones, nk, "+"
                        )
                else:
                    items = tuple(self._join_gather(el, j, jr, cols) for el in j.items)
                    out[j.result] = {"columns": items, "present": jr.present}

            # --- scalar reductions -------------------------------------------
            for sr in spec.scalar_reduces:
                expr = self._vec(sr.expr, cols, sr.table, arrays)
                mask = None
                if sr.match_field is not None:
                    mv = sr.match_value
                    if isinstance(mv, Const):
                        mval = jnp.asarray(mv.value)
                    elif isinstance(mv, Var):
                        mval = cols["__params__"][mv.name]
                    else:
                        raise UnsupportedProgram(f"match value {mv!r}")
                    mask = cols[sr.table][sr.match_field] == mval
                pmask = self._pred_mask(sr.filter_pred, cols, sr.table)
                if pmask is not None:
                    mask = pmask if mask is None else (mask & pmask)
                vals = jnp.broadcast_to(expr, cols_len_shape(cols, sr.table))
                if mask is not None:
                    vals = jnp.where(mask, vals, 0)
                out[sr.var] = jnp.sum(vals)

            # --- distinct reads (group-by result construction) -----------------
            for dr in spec.distinct_reads:
                nk = self.num_keys[(dr.table, dr.field)]
                pres = presence.get((dr.table, dr.field))
                if pres is None:
                    keys = cols[dr.table][dr.field]
                    pres = jax.ops.segment_sum(jnp.ones(keys.shape, jnp.int32), keys, num_segments=nk)
                key_ids = jnp.arange(nk, dtype=jnp.int32)
                items = []
                for el in dr.items:
                    items.append(self._vec_distinct(el, dr, key_ids, arrays, cols))
                present = pres > 0
                if dr.filter_pred is not None:
                    guard = self._vec_distinct(dr.filter_pred, dr, key_ids, arrays, cols)
                    present = present & guard.astype(bool)
                out[dr.result] = {"columns": tuple(items), "present": present}

            # --- filter/project -------------------------------------------------
            for fp in spec.filter_projects:
                mask = self._pred_mask(fp.filter_pred, cols, fp.table)
                items = tuple(self._vec(el, cols, fp.table, arrays) for el in fp.items)
                n = cols_len_shape(cols, fp.table)[0]
                if mask is None:
                    mask = jnp.ones((n,), bool)
                out[fp.result] = {"columns": items, "present": mask}

            self.segreduce_path, self._launches = self._launches, None
            return out

        return _named(run, program_name(spec))

    # distinct-read item: FieldRef(table,i,field) -> key ids;
    # ArrayRead(arr, FieldRef(...field)) -> arrays[arr][key_ids]
    def _vec_distinct(self, e: Expr, dr: DistinctReadSpec, key_ids, arrays, cols):
        if isinstance(e, FieldRef):
            if e.field == dr.field:
                return key_ids
            raise UnsupportedProgram("distinct read of a non-key field")
        if isinstance(e, ArrayRead):
            return arrays[e.array][self._vec_distinct(e.key, dr, key_ids, arrays, cols)]
        if isinstance(e, BinOp):
            return _jnp_binop(
                e.op,
                self._vec_distinct(e.lhs, dr, key_ids, arrays, cols),
                self._vec_distinct(e.rhs, dr, key_ids, arrays, cols),
            )
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        raise UnsupportedProgram(f"distinct item {e!r}")

    # -- parallel aggregation (the forall execution strategies) -----------------
    def _parallel_aggregate(self, keys, values, nk: int, op: str, n_valid: Optional[int]):
        """``n_valid``: the rows of a table placed on the mesh (``mesh_rows``),
        whose columns arrive as row shards padded past it; None for a table
        held whole."""
        if n_valid is not None:
            return self._mesh_aggregate(keys, values, nk, op, n_valid)
        if self.choices.parallel != "vmap" or self.spec.n_parts <= 1:
            return self._aggregate(keys, values, nk, op)
        n = self.spec.n_parts
        pad = (-len(keys)) % n
        if pad:
            keys = jnp.concatenate([keys, jnp.zeros((pad,), keys.dtype)])
            # pad with the op identity, not 0 — a padded 0 lands in segment 0
            # and corrupts its max/min exactly like an unmasked filtered row
            fill = jnp.full((pad,), _op_identity(op, values.dtype), values.dtype)
            values = jnp.concatenate([values, fill])
        partials = jax.vmap(lambda k, v: self._aggregate(k, v, nk, op))(
            keys.reshape(n, -1), values.reshape(n, -1)
        )
        if op == "+":
            return partials.sum(0)
        return partials.max(0) if op == "max" else partials.min(0)

    def _mesh_aggregate(self, keys, values, nk: int, op: str, n_valid: int):
        """SPMD over the mesh axis: each device reduces the rows it holds,
        then one collective (psum/pmax/pmin, the partitioned merge's
        analogue) leaves the combined accumulator on every device."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        if n_valid < keys.shape[0]:
            # rows Plan padded past the table: key 0 with the op identity
            valid = jnp.arange(keys.shape[0], dtype=jnp.int32) < n_valid
            keys = jnp.where(valid, keys, 0)
            values = jnp.where(valid, values, _op_identity(op, values.dtype))
        ax = self.choices.axis_name
        combine = getattr(jax.lax, _COMBINE[op])

        def local(k, v):
            return combine(self._aggregate(k, v, nk, op), ax)

        # check_vma=False: a pallas_call's out shapes carry no varying-axes
        # type, so the checked mode cannot type the segreduce kernel; the
        # combine above makes the result the same on every device
        f = shard_map(
            local, mesh=self.choices.mesh, in_specs=(P(ax), P(ax)), out_specs=P(), check_vma=False
        )
        return f(keys, values)

    # -- equi-join engine --------------------------------------------------------
    #
    # The build side is sorted once; probes binary-search it.  With a
    # key-unique build side one searchsorted gives the single candidate row
    # ('lookup').  With duplicate keys the [left, right) searchsorted pair
    # bounds each probe's match run, and the output is expanded to the
    # static shape (probe_rows × M) where M is the max key multiplicity
    # measured at compile time ('expand'); absent slots are masked out.
    # A dense unique integer build key (``key_range``) needs no search: a
    # table over its domain gives each probe its row.

    def _join_rows(
        self, j: JoinSpec, mult: int, cols, build_sorted=None, n_valid_build=None,
        key_range: Optional[Tuple[int, int]] = None,
    ) -> "_JoinRows":
        """``build_sorted`` is an optional precomputed ``(order, sorted_keys)``
        of the build side in ``cols`` — chunked executors that probe the same
        build partition many times pass it to sort once per partition.

        ``n_valid_build`` marks the build side as *padded*: only the first
        ``n_valid_build`` sorted rows are real (the rest carry a maximal key
        sentinel), so match runs are clipped to it.  Padding sorts to the
        end, which keeps every real match run inside the valid prefix even
        when real keys equal the sentinel value.

        ``key_range`` is the build key's ``(kmin, kmax)`` from
        ``join_key_range``: the probe goes through a position table."""
        bk = cols[j.build_table][j.build_key]
        pk = cols[j.probe_table][j.probe_fk]
        n_probe = pk.shape[0]
        pmask = self._pred_mask(j.probe_filter, cols, j.probe_table)
        if bk.shape[0] == 0 or mult == 0:
            # empty build side: every probe misses (never index into the
            # zero-length build columns — gather would clamp to garbage)
            return _JoinRows(
                None, jnp.zeros((n_probe,), jnp.int32), jnp.zeros((n_probe,), bool), True
            )
        if key_range is not None:
            kmin, kmax = key_range
            # a key outside the domain misses; it never clamps onto a slot
            pk = pk.astype(jnp.int32)
            inr = (pk >= kmin) & (pk <= kmax)
            # M == 1 is exact for the data compiled for, so the unique key
            # gives the 1:1 layout a width-1 expansion computes
            rows = jnp.full((kmax - kmin + 1,), -1, jnp.int32).at[bk - kmin].set(
                jnp.arange(bk.shape[0], dtype=jnp.int32), unique_indices=True
            )[jnp.where(inr, pk - kmin, 0)]
            present = inr & (rows >= 0)
            if pmask is not None:
                present = present & pmask
            return _JoinRows(None, jnp.maximum(rows, 0), present, False)
        if build_sorted is not None:
            order, sk = build_sorted
        else:
            order = jnp.argsort(bk)
            sk = bk[order]
        expand = self.choices.join_method == "expand" or mult > 1
        if not expand:
            pos = jnp.clip(jnp.searchsorted(sk, pk), 0, sk.shape[0] - 1)
            present = sk[pos] == pk
            if n_valid_build is not None:
                present = present & (pos < n_valid_build)
            if pmask is not None:
                present = present & pmask
            return _JoinRows(None, order[pos], present, False)
        lo = jnp.searchsorted(sk, pk, side="left")
        hi = jnp.searchsorted(sk, pk, side="right")
        if n_valid_build is not None:
            lo = jnp.minimum(lo, n_valid_build)
            hi = jnp.minimum(hi, n_valid_build)
        counts = hi - lo
        # the (probe_rows × M) slot space, probe-row-major, built 1-D: an
        # (n_probe, M) array with a small M is padded to 128 lanes on a TPU
        slot_ids = jnp.arange(n_probe * mult, dtype=jnp.int32)
        probe_idx = slot_ids // mult
        slot = slot_ids - probe_idx * mult
        pos = jnp.clip(lo[probe_idx] + slot, 0, sk.shape[0] - 1)
        present = slot < counts[probe_idx]
        if pmask is not None:
            present = present & pmask[probe_idx]
        return _JoinRows(probe_idx, order[pos], present, False)

    def _join_gather(self, e: Expr, j: JoinSpec, jr: "_JoinRows", cols):
        """Vectorize an expression over the joined (probe, build) row pairs."""
        if isinstance(e, FieldRef):
            if e.loopvar == j.probe_var:
                col = cols[j.probe_table][e.field]
                return col if jr.probe_idx is None else col[jr.probe_idx]
            if e.loopvar == j.build_var:
                col = cols[j.build_table][e.field]
                if jr.empty_build:
                    col = jnp.zeros((1,), col.dtype)
                return col[jr.build_rows]
            raise UnsupportedProgram(f"join item var {e.loopvar}")
        if isinstance(e, Const):
            return jnp.asarray(e.value)
        if isinstance(e, Var):
            params = cols.get("__params__", {})
            if e.name in params:
                return params[e.name]
            raise UnsupportedProgram(f"free Var {e.name} in join expr")
        if isinstance(e, BinOp):
            return _jnp_binop(
                e.op, self._join_gather(e.lhs, j, jr, cols), self._join_gather(e.rhs, j, jr, cols)
            )
        raise UnsupportedProgram(f"join item {e!r}")


@dataclass
class _JoinRows:
    """Row pairing produced by the join engine, in static (padded) shape.

    probe_idx is None when output slots align 1:1 with probe rows (lookup
    path / empty build); otherwise it gathers the probe side into the
    expanded (probe_rows × M) slot space."""

    probe_idx: Optional[jnp.ndarray]
    build_rows: jnp.ndarray
    present: jnp.ndarray
    empty_build: bool


# ===========================================================================
# Plan — user-facing compiled program
# ===========================================================================


class Plan:
    """A compiled forelem program.  ``run(db)`` executes on a Database and
    densifies multiset results back to Python tuples (for comparison with the
    reference interpreter); ``fn`` is the raw jitted callable.

    ``resident``: the ``ResidentColumns`` store a ``Session`` attaches; with
    it, a column an earlier plan placed is read from the device instead of
    copied again.  A plan run outside a session places every column on
    every run.

    ``upload_bytes``: the bytes the last ``input_columns`` placed, and
    ``resident_bytes`` the bytes it served from the store, by placement
    (``sharded`` over the mesh, ``single`` on the default device);
    ``resident_reads``: its columns by ``hit``/``miss`` in the store."""

    def __init__(self, program: Program, db: Database, choices: Optional[CodegenChoices] = None, jit: bool = True):
        self.program = program
        self.db = db
        self.lowering = JaxLowering(program, db, choices)
        raw = self.lowering.build()
        self.fn = jax.jit(raw) if jit else raw
        c = self.lowering.choices
        self._rows_sharding = (
            jax.sharding.NamedSharding(c.mesh, jax.sharding.PartitionSpec(c.axis_name))
            if self.lowering.mesh_rows
            else None
        )
        self.resident: Optional[ResidentColumns] = None
        self.upload_bytes: Dict[str, int] = {}
        self.resident_bytes: Dict[str, int] = {}
        self.resident_reads: Dict[str, int] = {}

    @property
    def n_devices(self) -> int:
        """Devices the input columns are placed on."""
        s = self._rows_sharding
        return 1 if s is None else s.mesh.shape[self.lowering.choices.axis_name]

    def input_columns(self) -> Dict[str, Dict[str, jnp.ndarray]]:
        wanted: Dict[Key, Callable[[], jax.Array]] = {}
        where: Dict[Key, Tuple[str, str, str]] = {}  # key -> (table, field, placement)
        needed = required_columns(self.program, self.lowering.spec)
        for t, fields in needed.items():
            if t not in self.db:
                continue
            ms = self.db[t]
            sharded = t in self.lowering.mesh_rows
            for f in fields:
                if f in ms.columns:
                    key = (ms.uid, f, self._rows_sharding if sharded else None)
                    wanted[key] = functools.partial(self._place, ms, f, sharded)
                    where[key] = (t, f, "sharded" if sharded else "single")
        if self.resident is None:
            got = {k: (place(), False) for k, place in wanted.items()}
        else:
            got = self.resident.fetch(wanted)
        cols: Dict[str, Dict[str, jnp.ndarray]] = {t: {} for t in needed if t in self.db}
        placed: Dict[str, int] = {}
        resident: Dict[str, int] = {}
        reads: Dict[str, int] = {}
        for key, (col, hit) in got.items():
            t, f, placement = where[key]
            cols[t][f] = col
            into = resident if hit else placed
            into[placement] = into.get(placement, 0) + col.nbytes
            result = "hit" if hit else "miss"
            reads[result] = reads.get(result, 0) + 1
        self.upload_bytes, self.resident_bytes = placed, resident
        self.resident_reads = reads
        return cols

    def _place(self, ms: Multiset, field: str, sharded: bool) -> jax.Array:
        host = ms.field(field)
        if sharded:
            return _place_rows(np.asarray(host), self._rows_sharding, self.n_devices)
        return jnp.asarray(host)

    def run(
        self, params: Optional[Dict[str, Any]] = None, *, tracer: Any = None
    ) -> Dict[str, Any]:
        if tracer is None or not tracer.enabled:
            cols = self.input_columns()
            if params:
                cols["__params__"] = {k: jnp.asarray(v) for k, v in params.items()}
            raw = self.fn(cols)
            out = {k: _densify(v) for k, v in raw.items() if k in self.program.results}
            return apply_order_limit(self.program, out)
        with tracer.span("jax.upload", devices=self.n_devices):
            cols = self.input_columns()
            if params:
                cols["__params__"] = {k: jnp.asarray(v) for k, v in params.items()}
            jax.block_until_ready(cols)  # the copy ends here, not in jax.compute
        with tracer.span("jax.compute"):
            raw = self.fn(cols)
            jax.block_until_ready(raw)  # traced runs attribute device time here
        with tracer.span("densify"):
            out = {k: _densify(v) for k, v in raw.items() if k in self.program.results}
            return apply_order_limit(self.program, out)


def _place_rows(host: np.ndarray, sharding: Any, n_devices: int) -> jax.Array:
    """``host`` split by rows over ``sharding``'s mesh axis, each device sent
    only its own rows; the last shard is padded with zeros up to a multiple
    of ``n_devices`` (the lowering masks rows past the table's count)."""
    total = -(-len(host) // n_devices) * n_devices

    def rows(index: Tuple[slice, ...]) -> np.ndarray:
        start, stop, _ = index[0].indices(total)
        part = host[start:stop]
        if len(part) < stop - start:
            part = np.concatenate([part, np.zeros(stop - start - len(part), host.dtype)])
        return part

    return jax.make_array_from_callback((total,), sharding, rows)


class JaxBackend:
    """The default production backend: vectorized, jitted JAX execution with
    the full ``CodegenChoices`` strategy space."""

    name = "jax"

    def compile(self, program: Program, db: Database, choices: Optional[CodegenChoices] = None) -> Plan:
        return Plan(program, db, choices)


register_backend(JaxBackend())
