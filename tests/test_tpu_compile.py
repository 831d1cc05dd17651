# Compile the engine's device programs at real widths for a described TPU
# v5e, with no chip attached: the Mosaic-compiled segreduce kernel at 2^25
# rows and 2048, 3000 or 2^20 keys on both of its paths, and a whole
# expand-join plan.  What interpret mode cannot show — an op Mosaic refuses,
# a layout that inflates HBM use, a program that does not fit the chip —
# fails here.
#
# The topology is described inside a module-scoped fixture, never while a
# module is imported: only one process may load the TPU library at a time.
import importlib.util
import re
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import OptimizeOptions, optimize, sql_to_forelem
from repro.data.multiset import Database, Multiset
from repro.kernels.segreduce.kernel import fused_segreduce_pallas, kernel_path
from repro.kernels.segreduce.ops import ENGINE_TILE

ROWS = 1 << 25
NUM_KEYS = 3000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the only reason to skip: no TPU library is installed to compile with.
    # Any failure to describe the topology with it installed fails the tests.
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("libtpu is not installed")
    desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a compile for a described chip can be written to the persistent cache
    # but not read back; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


def _shape(n, dtype, sharding):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


KERNEL_CASES = pytest.mark.parametrize(
    "dtypes,ops,with_presence,masked",
    [
        ((jnp.float32,) * 3, ("sum", "max", "min"), True, True),
        # SQL COUNT: an int32 sum of ones
        ((jnp.int32,), ("sum",), True, False),
        ((jnp.int32,), ("max",), False, False),
    ],
    ids=["f32_sum_max_min_presence", "i32_sum_count", "i32_max"],
)


def _compile_kernel(sharding, num_keys, dtypes, ops, with_presence, masked):
    fn = partial(
        fused_segreduce_pallas, ops=ops, num_keys=num_keys,
        with_presence=with_presence, tile=ENGINE_TILE, interpret=False,
    )
    keys = _shape(ROWS, jnp.int32, sharding)
    values = tuple(_shape(ROWS, dt, sharding) for dt in dtypes)
    mask = _shape(ROWS, jnp.bool_, sharding) if masked else None
    compiled = jax.jit(lambda k, v, m: fn(k, v, mask=m)).lower(keys, values, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled.memory_analysis()


@KERNEL_CASES
def test_segreduce_kernel_compiles_and_fits(one_chip, dtypes, ops, with_presence, masked):
    mem = _compile_kernel(one_chip, NUM_KEYS, dtypes, ops, with_presence, masked)
    # lane-dense blocks: the kernel's working copies stay near the data's size
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes


@KERNEL_CASES
def test_segreduce_kernel_output_dense_at_large_key_space(
    one_chip, dtypes, ops, with_presence, masked
):
    """2^20 keys: the kernel folds its lane partials itself, so each
    accumulator leaves it at 4 bytes a key; 128 lane partials a key would
    take 512 MiB of temp per accumulator."""
    mem = _compile_kernel(one_chip, 1 << 20, dtypes, ops, with_presence, masked)
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes


@pytest.mark.parametrize("masked", [False, True], ids=["unmasked", "masked"])
@pytest.mark.parametrize("num_keys", [2048, 3000])
def test_segreduce_mxu_path_compiles_and_fits(one_chip, num_keys, masked):
    """The BDB aggregation's launch (an f32 SUM and the presence count) on
    the MXU path: the row contraction's operand layouts compile, and the
    one-hots stay in VMEM — HBM holds at most the masked key column beside
    the arguments, where a one-hot of 2^25 rows would take gigabytes."""
    assert kernel_path(("sum",), [jnp.float32], num_keys) == "mxu"
    mem = _compile_kernel(one_chip, num_keys, (jnp.float32,), ("sum",), True, masked)
    assert mem.temp_size_in_bytes <= (4 * ROWS if masked else 0) + (1 << 20)


def test_expand_join_plan_fits_one_chip(one_chip):
    """A duplicate-key (expansion) join over 2^25 probe rows: the flat
    (probe rows × multiplicity) slot space must fit one chip's HBM."""
    rng = np.random.default_rng(0)
    n, n_servers = 4096, 200
    db = Database()
    db.add(Multiset.from_columns(
        "logs",
        url_id=rng.integers(0, NUM_KEYS, n).astype(np.int32),
        status=rng.choice(np.array([200, 500], np.int32), n),
        server_id=rng.integers(0, n_servers, n).astype(np.int32),
    ))
    db.add(Multiset.from_columns(
        "mirrors",
        id=np.repeat(np.arange(n_servers, dtype=np.int32), 2),
        host=rng.integers(0, 1000, 2 * n_servers).astype(np.int32),
    ))
    prog = sql_to_forelem(
        "SELECT l.url_id, m.host FROM logs l, mirrors m "
        "WHERE l.server_id = m.id AND l.status = 500",
        {t: db[t].field_names() for t in db.tables},
    )
    plan = optimize(prog, db, OptimizeOptions(reformat=False)).plan
    cols = {
        t: {f: _shape(ROWS if t == "logs" else a.shape[0], a.dtype, one_chip) for f, a in fs.items()}
        for t, fs in plan.input_columns().items()
    }
    mem = plan.fn.lower(cols).compile().memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * mem.argument_size_in_bytes


def test_device_programs_keep_their_names_on_the_chip(one_chip):
    """A chunk kernel's module is ``jit_chunk_fused_agg`` and its Pallas
    call the op ``%segreduce``, the names a v5e trace's ``XLA Modules`` and
    ``XLA Ops`` lines carry."""
    from repro.backends.jax_vec import _named
    from repro.kernels.segreduce import ops as segops

    def fn(keys, values, mask):
        return segops._fused_impl(keys, (values,), mask, ("sum",), NUM_KEYS, True, "compiled")

    n = 1 << 16
    text = jax.jit(_named(fn, "chunk_fused_agg")).lower(
        _shape(n, jnp.int32, one_chip), _shape(n, jnp.float32, one_chip),
        _shape(n, jnp.bool_, one_chip),
    ).compile().as_text()
    assert text.startswith("HloModule jit_chunk_fused_agg")
    assert re.search(r"%segreduce(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"", text)


def test_mesh_groupby_fits_four_chips(four_chips, monkeypatch):
    """The BDB aggregation over four chips: 2^27 rows arrive as one 2^25-row
    shard a chip, each chip runs the kernel over its own rows, and one
    all-reduce per accumulator (the sum, the presence count) combines them;
    no row column moves between chips."""
    from repro.kernels.segreduce import ops as segops

    monkeypatch.setattr(segops, "pallas_mode", lambda: "compiled")
    rows, n = 1 << 27, 4096
    db = Database().add(Multiset.from_columns(
        "uservisits",
        ip7=(np.arange(n) % 2048).astype(np.int32),
        adRevenue=np.random.default_rng(0).random(n).astype(np.float32),
    ))
    prog = sql_to_forelem("SELECT ip7, SUM(adRevenue) FROM uservisits GROUP BY ip7",
                          {"uservisits": ["ip7", "adRevenue"]})
    plan = optimize(prog, db, OptimizeOptions(
        n_parts=4, agg_method="kernel", parallel_exec="shard_map", mesh=four_chips,
        reformat=False)).plan
    assert plan.lowering.mesh_rows == {"uservisits": n}
    plan.lowering.mesh_rows["uservisits"] = rows  # the cell's table, before the trace
    sharded = NamedSharding(four_chips, PartitionSpec("data"))
    cols = {"uservisits": {"ip7": jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=sharded),
                           "adRevenue": jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=sharded)}}
    compiled = plan.fn.lower(cols).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert len(re.findall(r" all-reduce(-start)?\(", text)) == 2
    assert not re.search(r"(all-gather|all-to-all|collective-permute|reduce-scatter)(-start)?\(", text)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == 2 * 4 * rows // 4
    assert mem.temp_size_in_bytes <= mem.argument_size_in_bytes
