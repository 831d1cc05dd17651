# Every device program the engine jits carries a stable name taken from
# what it computes, so a device trace's ``XLA Modules`` line can tell the
# programs apart: ``q_<operators>`` for the monolithic plan, ``chunk_<op>``
# for the partitioned chunk kernels.
import numpy as np
import pytest

from repro import Session
from repro.backends.codegen import extract_spec
from repro.backends.jax_vec import program_name
from repro.frontends.sql import sql_to_forelem

SCHEMAS = {"t": ["k", "v"], "d": ["id", "w"]}
QUERIES = {
    "q_groupby": "SELECT k, SUM(v) FROM t GROUP BY k",
    "q_scalar": "SELECT SUM(v) FROM t WHERE k < 10",
    "q_join_groupby": "SELECT t.k, SUM(d.w) FROM d, t WHERE d.id = t.k GROUP BY t.k",
    "q_project": "SELECT k, v FROM t WHERE k < 3",
}


def _session(**kw):
    rng = np.random.default_rng(0)
    s = Session(**kw)
    s.register("t", k=rng.integers(0, 40, 4000).astype(np.int32),
               v=rng.integers(0, 100, 4000).astype(np.int32))
    s.register("d", id=np.arange(40, dtype=np.int32), w=np.arange(40, dtype=np.int32) * 2)
    return s


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_program_name_from_the_plans_operators(name):
    assert program_name(extract_spec(sql_to_forelem(QUERIES[name], SCHEMAS))) == name


@pytest.mark.parametrize("name", sorted(QUERIES))
def test_monolithic_module_is_named(name):
    s = _session(backend="jax")
    r = s.sql(QUERIES[name])
    plan = r.plan
    assert plan.fn.__name__ == name
    text = plan.fn.lower(plan.input_columns()).as_text()
    assert f"jit_{name}" in text and "jit_run" not in text


def test_chunk_kernels_are_named():
    s = _session(backend="partitioned", n_partitions=3)
    names = set()
    for q in QUERIES.values():
        plan = s.sql(q).plan
        names |= {k._jit.__name__ for k in plan._kernels.values()}
    assert {"chunk_reduce", "chunk_join", "chunk_project"} <= names
    assert names - {"chunk_reduce", "chunk_join", "chunk_project"} in (
        {"chunk_agg"}, {"chunk_fused_agg"})


def test_names_are_the_same_across_runs():
    a = {q: _session(backend="jax").sql(q).plan.fn.__name__ for q in QUERIES.values()}
    b = {q: _session(backend="jax").sql(q).plan.fn.__name__ for q in QUERIES.values()}
    assert a == b


@pytest.mark.parametrize("method,name", [("dense", "chunk_agg"), ("kernel", "chunk_fused_agg")])
def test_group_by_chunk_kernel_names(method, name):
    from repro.backends import PartitionedChoices, get_backend
    from repro.backends.jax_vec import CodegenChoices
    from repro.data.multiset import Database, Multiset

    db = Database().add(Multiset.from_columns(
        "t", k=np.arange(100, dtype=np.int32) % 7, v=np.ones(100, np.int32)))
    plan = get_backend("partitioned").compile(
        sql_to_forelem(QUERIES["q_groupby"], SCHEMAS), db,
        PartitionedChoices(base=CodegenChoices(agg_method=method), n_partitions=2))
    plan.run()
    assert {k._jit.__name__ for k in plan._kernels.values()} == {name}
