"""The main path's kernels compile for the chip — checked without one.

The TPU's compiler is installed in the sandbox and compiles for a
DESCRIBED v5e (``jax.experimental.topologies``), so these tests guard
every PR against what interpret mode and the CPU backend cannot see: a
Mosaic refusal, a program that does not fit HBM, a collective that does
not partition. Nothing runs, so they say nothing about results or
times. One file on purpose: only one process may hold the TPU library,
the worker that is handed this file loads it, and a second file could
land on another worker and skip in silence. The topology is described
inside a fixture — never at import (every xdist worker imports every
test file).

Sort-bearing programs stay at the 1,024-row rung: measured with this
compiler (jax 0.9.0 / libtpu 0.0.34), every program holding a
``lax.sort`` over >= 64K rows takes 17-190 s to compile for the TPU
(int32 pair sort 17 s at 64K and 28 s at 1M rows; int64 join build 120 s
at 3M rows; the aggregate's multi-operand sort 41 s at 16K rows and
189 s at 1M), against 0.5 s at 1,024 rows — see ROADMAP queue S.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ballista_tpu.kernels import aggregate, join, mesh_shuffle
from ballista_tpu.kernels.aggregate import AggInput


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_disk_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one (the next run warns and
    recompiles), so keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _shape(sharding, n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def test_dense_xla_aggregate_q1_at_8m_rows(one_chip, no_disk_cache):
    """q1's partial aggregate (four scaled-int64 sums + count over the
    flag x status groups) at the 1<<23-row batch."""
    n = 1 << 23

    def q1_partial(gids, live, qty, price, disc_price, charge):
        aggs = [AggInput("sum", v, None)
                for v in (qty, price, disc_price, charge)]
        aggs.append(AggInput("count", None, None))
        return aggregate.dense_grouped_aggregate(gids, live, aggs, 8)

    s = functools.partial(_shape, one_chip, n)
    compiled = _compile(q1_partial, s(jnp.int32), s(jnp.bool_),
                        *[s(jnp.int64)] * 4)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "sorted"])
def test_join_probe_unique_at_1m_rows(one_chip, no_disk_cache, dense):
    n = 1 << 20
    s = functools.partial(_shape, one_chip, n)
    scalar = functools.partial(jax.ShapeDtypeStruct, (), sharding=one_chip)

    def probe(sorted_keys, order, num_live, dense_rows, dense_base,
              probe_keys, probe_live):
        table = join.BuildTable(
            sorted_keys, order, num_live,
            dense_rows if dense else None,
            dense_base if dense else None)
        return join.probe_unique(table, probe_keys, probe_live)

    _compile(probe, s(jnp.int64), s(jnp.int32), scalar(jnp.int32),
             s(jnp.int32), scalar(jnp.int64), s(jnp.int64), s(jnp.bool_))


def test_join_probe_expand_at_q3_shapes(one_chip, no_disk_cache):
    """q3's expanding probe at SF3 standalone (131,072 probe rows, output
    capacity 131,072, a 2**22-row partition of int64 build keys: the
    ``join.search`` events of a chip run, PERF.md, PR 36; served it is
    32,768 over 2**21, the same two levels): every search goes
    down kernels/search.py's levels, so inside a ``while`` (the chunk
    loop's; ``jnp.searchsorted``'s was a loop of log2(n) dependent
    element gathers, 100 of the program's 102 ms on the chip: PERF.md,
    PR 36) every gather takes a whole row of 128; the element gathers
    that are left assemble the output, once a slot. Well under a minute:
    the program holds no sort."""
    import re
    import time

    nb, rows = 1 << 22, 131072
    scalar = functools.partial(jax.ShapeDtypeStruct, (), sharding=one_chip)

    def probe(sorted_keys, order, num_live, probe_keys, probe_live):
        table = join.BuildTable(sorted_keys, order, num_live)
        return join.probe_expand(table, probe_keys, probe_live, rows)

    started = time.monotonic()
    compiled = _compile(
        probe, _shape(one_chip, nb, jnp.int64), _shape(one_chip, nb, jnp.int32),
        scalar(jnp.int32), _shape(one_chip, rows, jnp.int64),
        _shape(one_chip, rows, jnp.bool_))
    assert time.monotonic() - started < 60
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
    looped = [g for g in re.findall(r" gather\(.*", text) if "/while/" in g]
    assert looped and all("slice_sizes={1,128}" in g for g in looped)


def test_join_probe_ranges_at_q3_shapes(one_chip, no_disk_cache):
    """The first half of q3's expanding probe (``join.ranges``): 131,072
    probe rows over a 2**22-row partition of build keys. Everything in
    it costs a probe row; its searches walk the levels a row of 128 at a
    time, and it holds no sort and no scatter."""
    import re
    import time

    nb, rows = 1 << 22, 131072
    scalar = functools.partial(jax.ShapeDtypeStruct, (), sharding=one_chip)

    def ranges(sorted_keys, order, num_live, probe_keys, probe_live):
        table = join.BuildTable(sorted_keys, order, num_live)
        return join.probe_ranges(table, probe_keys, probe_live)

    started = time.monotonic()
    compiled = _compile(
        ranges, _shape(one_chip, nb, jnp.int64),
        _shape(one_chip, nb, jnp.int32), scalar(jnp.int32),
        _shape(one_chip, rows, jnp.int64), _shape(one_chip, rows, jnp.bool_))
    assert time.monotonic() - started < 60
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
    gathers = re.findall(r" gather\(.*", text)
    assert gathers and all("slice_sizes={1,128}" in g for g in gathers)


def test_join_expand_slots_at_q3_shapes(one_chip, no_disk_cache):
    """The second half (``join.expand``) at the rung of q3's count:
    16,384 slots out of 131,072 probe rows and a 2**22-row build, with
    the assembly's gathers as ``JoinExec._assemble_expanded`` makes them
    (q3's output: three int64 and one int32 column of the probe side,
    three and three of the build side). Everything in it costs an output
    slot: no gather produces 131,072 elements, there is no sort and no
    scatter."""
    import re
    import time

    nb, rows, slots = 1 << 22, 131072, 16384
    scalar = functools.partial(jax.ShapeDtypeStruct, (), sharding=one_chip)
    probe_cols = [jnp.int64] * 3 + [jnp.int32]
    build_cols = [jnp.int64] * 3 + [jnp.int32] * 3

    def expand(sorted_keys, order, num_live, lo, ends, total, pcols, bcols):
        table = join.BuildTable(sorted_keys, order, num_live)
        prows, brows, olive = join.expand_slots(table, lo, ends, total,
                                                slots)
        return ([jnp.take(c, prows) for c in pcols]
                + [jnp.take(c, brows) for c in bcols], olive)

    started = time.monotonic()
    compiled = _compile(
        expand, _shape(one_chip, nb, jnp.int64),
        _shape(one_chip, nb, jnp.int32), scalar(jnp.int32),
        _shape(one_chip, rows, jnp.int32), _shape(one_chip, rows, jnp.int32),
        scalar(jnp.int32),
        [_shape(one_chip, rows, d) for d in probe_cols],
        [_shape(one_chip, nb, d) for d in build_cols])
    assert time.monotonic() - started < 60
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
    # "<name> = <type>[<output shape>]{..} gather(<operands>), ..."
    made = re.findall(r"= (\S+) gather\(", text)
    assert made and not any(str(rows) in shape for shape in made)
    assert any(str(slots) in shape for shape in made)


def test_sort_based_aggregate_at_first_rung(one_chip, no_disk_cache):
    """Two-key ``grouped_aggregate`` (the multi-operand lax.sort form) at
    1,024 rows only — see the module docstring for why not larger."""
    n = 1024

    def agg(k64, k32, live, v):
        return aggregate.grouped_aggregate(
            [k64, k32], live,
            [AggInput("sum", v, None), AggInput("count", None, None)],
            group_capacity=n)

    s = functools.partial(_shape, one_chip, n)
    _compile(agg, s(jnp.int64), s(jnp.int32), s(jnp.bool_), s(jnp.int64))


def test_join_build_sorted_at_first_rung(one_chip, no_disk_cache):
    """The int64 argsort join build at 1,024 rows only (module docstring:
    120 s at 3M rows)."""
    s = functools.partial(_shape, one_chip, 1024)
    _compile(join.build_sorted_with_unique, s(jnp.int64), s(jnp.bool_))


@pytest.mark.parametrize("size", [16384, 131072, 1 << 20])
def test_compact_perm_at_1m_rows_has_no_scatter(one_chip, no_disk_cache,
                                                size):
    """The join cells' compactions (2**20 rows to 16,384; to 131,072,
    where the queries go in chunks) and the mesh path's worst case
    (every row asked for). A scatter over the capacity is what
    ``jnp.nonzero`` cost on the chip (PERF.md, PR 29); a ``while`` is
    only the chunk loop's."""
    from ballista_tpu.physical.base import compact_perm

    compiled = _compile(functools.partial(compact_perm, size=size),
                        _shape(one_chip, 1 << 20, jnp.bool_))
    text = compiled.as_text()
    assert " scatter(" not in text and " sort(" not in text
    assert (" while(" in text) == (size > 65536)


@pytest.mark.parametrize("rows,slots", [(4096, 1024), (1 << 20, 1 << 18)],
                         ids=["4k_rows", "1m_rows"])
def test_mesh_all_to_all_rows_on_four_chips(topo, no_disk_cache, rows,
                                            slots):
    """The ICI shuffle as ONE program across the four described chips:
    the compiler must place an all-to-all, not gather to one device; and
    the pack that fills the send buffers holds no ``sort`` and no
    ``scatter`` (it searches the running count a destination,
    ``kernels/search.py`` ``first_live``, and gathers): at 2**20 rows a
    device and slots of a quarter, an int64 key, an int64 and an int32
    payload and two validity planes, which travel as one word."""
    from jax import shard_map

    n_dev = 4
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("data"),) * 6,
                       out_specs=(P("data"),) * 5, check_vma=False)
    def exchange(keys, vals, dates, valid_a, valid_b, live):
        dest = mesh_shuffle.destination_ids(keys, live, n_dev)
        cols, out_live, counts = mesh_shuffle.all_to_all_rows(
            [keys, vals, dates, valid_a, valid_b], live, dest, "data",
            n_dev, dest_capacity=slots)
        return cols[0], cols[2], cols[4], out_live, counts

    s = functools.partial(_shape, sharded, n_dev * rows)
    compiled = _compile(exchange, s(jnp.int64), s(jnp.int64), s(jnp.int32),
                        s(jnp.bool_), s(jnp.bool_), s(jnp.bool_))
    text = compiled.as_text()
    assert "all-to-all" in text
    assert " sort(" not in text and " scatter(" not in text
    # key, value, date, one word of validity bits, and the counts
    assert text.count(" all-to-all(") <= 6


def test_join_probe_after_compaction_at_16384_rows(one_chip, no_disk_cache,
                                                   monkeypatch):
    """q14's shape since PR 32: the month filter fused into the probe side
    keeps 1.2% of a 2**20-row lineitem batch, so ``JoinExec`` compacts to
    16,384 rows BEFORE the probe and the probe program (dense-table
    gather, one gather a build column) is traced at that capacity,
    without the chain. The batches are made and the choice is taken on
    the CPU; the program it chose is then compiled for the chip."""
    from ballista_tpu import Int32, Int64, col, lit, schema
    from ballista_tpu.io import MemTableSource
    from ballista_tpu.physical import base
    from ballista_tpu.physical.fusion import fuse_plan
    from ballista_tpu.physical.join import JoinExec
    from ballista_tpu.physical.operators import FilterExec, ScanExec

    monkeypatch.setattr(base, "_SYNC_COST", [0.0])
    rng = np.random.default_rng(14)
    parts, rows = 600_000, 1 << 20
    part = MemTableSource.from_pydict(
        schema(("p_partkey", Int64), ("p_type", Int32)),
        {"p_partkey": np.arange(1, parts + 1),
         "p_type": rng.integers(0, 150, parts)})
    lineitem = MemTableSource.from_pydict(
        schema(("l_partkey", Int64), ("l_extendedprice", Int64),
               ("l_discount", Int64), ("l_shipdate", Int32)),
        {"l_partkey": rng.integers(1, parts + 1, rows),
         "l_extendedprice": rng.integers(90_000, 10_000_000, rows),
         "l_discount": rng.integers(0, 11, rows),
         "l_shipdate": rng.integers(8036, 8036 + 2526, rows)})
    j = fuse_plan(JoinExec(
        ScanExec("part", part),
        FilterExec((col("l_shipdate") >= lit(9374))
                   & (col("l_shipdate") < lit(9404)),
                   ScanExec("lineitem", lineitem)),
        [("p_partkey", "l_partkey")], "inner"))
    table, bb, unique, _, mode, key_tables, *_ = j._materialize_build(0)
    (pb, remaps, chained), = j._probe_inputs(bb, j.probe.execute(0))
    assert chained and unique and table.dense_rows is not None
    assert pb.capacity == 16384 and 10_000 < int(pb.num_rows) < 16384

    fn = j._unique_program(mode, chained)
    jitted = getattr(fn, "gf", fn).fn
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (table, bb, pb, key_tables, remaps))
    assert jax.eval_shape(jitted, *shapes).capacity == 16384
    jitted.lower(*shapes).compile()


@pytest.mark.parametrize("shape", ["q1_grouped", "q6_scalar"])
def test_aggregate_over_a_partition_of_five_batches(one_chip, no_disk_cache,
                                                    shape):
    """The scan-aggregate cells' partial stage since PR 40: a partition of
    SF3's lineitem arrives from the table cache as four batches of 1<<20
    rows and a tail rung of 1<<19, and the governed program takes the
    TUPLE and lays the pieces end to end in its own trace
    (``physical/base.py`` ``gather_batches``). The plan is made and the
    path chosen on the CPU over a few rows; the program is then compiled
    for the chip at the cell's capacities. It holds the assembled columns
    as temporaries (XLA keeps the copy: PERF.md, PR 40), which must fit
    beside the resident table."""
    from ballista_tpu import (Date32, Decimal, Utf8, avg, col, count, lit,
                              schema, sum_)
    from ballista_tpu.columnar import ColumnBatch, Dictionary
    from ballista_tpu.io import MemTableSource
    from ballista_tpu.physical.aggregate import HashAggregateExec
    from ballista_tpu.physical.fusion import fuse_plan
    from ballista_tpu.physical.operators import FilterExec, ScanExec

    s = schema(("l_quantity", Decimal(2)), ("l_extendedprice", Decimal(2)),
               ("l_discount", Decimal(2)), ("l_tax", Decimal(2)),
               ("l_returnflag", Utf8), ("l_linestatus", Utf8),
               ("l_shipdate", Date32))
    n = 64
    small = ColumnBatch.from_numpy(
        s, {"l_quantity": np.full(n, 1700), "l_extendedprice": np.full(n, 9),
            "l_discount": np.full(n, 5), "l_tax": np.full(n, 2),
            "l_returnflag": np.arange(n, dtype=np.int32) % 3,
            "l_linestatus": np.arange(n, dtype=np.int32) % 2,
            "l_shipdate": np.arange(n, dtype=np.int32) + 9000},
        {"l_returnflag": Dictionary(["A", "N", "R"]),
         "l_linestatus": Dictionary(["F", "O"])})
    scan = ScanExec("lineitem", MemTableSource(s, [[small]]))
    price, disc = col("l_extendedprice"), col("l_discount")
    if shape == "q1_grouped":
        st = fuse_plan(HashAggregateExec(
            "partial", [col("l_returnflag"), col("l_linestatus")],
            [sum_(col("l_quantity")), sum_(price),
             sum_(price * (lit(1) - disc)),
             sum_(price * (lit(1) - disc) * (lit(1) + col("l_tax"))),
             avg(col("l_quantity")), avg(price), avg(disc), count()],
            FilterExec(col("l_shipdate") <= lit(10471), scan)))
        fn = st.governed_jit(("agg.grouped", st.group_capacity),
                             st._grouped_build(st.group_capacity))
    else:
        st = fuse_plan(HashAggregateExec(
            "partial", [], [sum_(price * disc)],
            FilterExec((col("l_shipdate") >= lit(8766))
                       & (col("l_shipdate") < lit(9131))
                       & (col("l_quantity") < lit(24)), scan)))
        fn = st.governed_jit(("agg.scalar",), st._scalar_build())
    jitted = getattr(fn, "gf", fn).fn
    caps = (1 << 20,) * 4 + (1 << 19,)
    pieces = tuple(
        jax.tree_util.tree_map(
            lambda x, c=c: jax.ShapeDtypeStruct(
                (c,) if x.ndim else (), x.dtype, sharding=one_chip), small)
        for c in caps)
    compiled = jitted.lower(pieces).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 100e6  # the five pieces themselves
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 1e9


@pytest.mark.parametrize("keys", [("l_orderkey",), ("c_mktsegment",), ()],
                         ids=["hash_int64", "hash_utf8", "round_robin"])
def test_shuffle_dest_at_1m_rows_is_elementwise(one_chip, no_disk_cache,
                                                keys):
    """The shuffle write's one device step a batch since PR 44
    (``physical/operators.py`` ``shuffle_dest_program``): every row's
    destination, at a served scan's 1<<20-row batch, with the fan-out
    and the round-robin offset as operands. Elementwise only: no sort,
    no scatter, no loop over the capacity (a ``cumsum`` over 1<<20 rows
    is 22-33 s of compiling, PERF.md PR 29), one byte a row out."""
    from ballista_tpu import Date32, Decimal, Int64, Utf8, col, schema
    from ballista_tpu.columnar import ColumnBatch, Dictionary
    from ballista_tpu.physical.operators import shuffle_dest_program

    s = schema(("l_orderkey", Int64), ("l_extendedprice", Decimal(2)),
               ("l_discount", Decimal(2)), ("c_mktsegment", Utf8),
               ("l_shipdate", Date32))
    n = 64
    small = ColumnBatch.from_numpy(
        s, {"l_orderkey": np.arange(n), "l_extendedprice": np.full(n, 9),
            "l_discount": np.full(n, 5),
            "c_mktsegment": np.arange(n, dtype=np.int32) % 3,
            "l_shipdate": np.arange(n, dtype=np.int32) + 9000},
        {"c_mktsegment": Dictionary(["AUTOMOBILE", "BUILDING", "MACHINERY"])})
    fn = shuffle_dest_program(s, [col(k) for k in keys], 17)
    batch = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((1 << 20,) if x.ndim else (), x.dtype,
                                       sharding=one_chip), small)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = getattr(fn, "gf", fn).fn.lower(batch, scalar, scalar).compile()
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
    assert " while(" not in text
    assert compiled.memory_analysis().output_size_in_bytes == 1 << 20
