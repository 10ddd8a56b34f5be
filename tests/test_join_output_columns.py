"""A join emits only the columns its parent reads: the optimizer's pruning
pass hands ``logical.Join`` a column list as it hands ``TableScan`` a
projection, the planner hands it on as ``JoinExec.out_columns``, and the
assembly gathers one column a name of it. Held here: the lists q3 and q14
get, that answers do not change with the list, that the list is part of a
join's compiled identity, that it survives the wire and the scheduler's
mesh fusion, and that the events which say what was gathered carry it."""

import dataclasses
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from ballista_tpu import Int64, Utf8, col, lit, schema
from ballista_tpu.client import BallistaContext
from ballista_tpu.columnar import Column, ColumnBatch
from ballista_tpu.io import MemTableSource
from ballista_tpu.logical import Join, LogicalPlanBuilder
from ballista_tpu.optimizer import optimize
from ballista_tpu.physical.join import JoinExec
from ballista_tpu.physical.operators import ScanExec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _joins(plan):
    """Every join of a logical or physical plan, top first."""
    found = [plan] if isinstance(plan, (Join, JoinExec)) else []
    for c in plan.children():
        found += _joins(c)
    return found


# -- (a) what the pruning pass hands a join --------------------------------


@pytest.fixture(scope="module")
def tpch_ctx():
    from benchmarks.tpch.schema_def import TPCH_SCHEMAS

    ctx = BallistaContext.standalone()
    for name, s in TPCH_SCHEMAS.items():
        ctx.register_source(name, MemTableSource.from_pydict(
            s, {f.name: [] for f in s.fields}))
    return ctx


def _query(name):
    return open(os.path.join(ROOT, "perfbench", "queries",
                             f"{name}.sql")).read().rstrip().rstrip(";")


# join keys -> the columns the join's parent reads (ISSUE 42's table)
EMITTED = {
    ("q14", "l_partkey=p_partkey"):
        {"l_extendedprice", "l_discount", "p_type"},
    ("q3", "c_custkey=o_custkey"):
        {"o_orderkey", "o_orderdate", "o_shippriority"},
    ("q3", "l_orderkey=o_orderkey"):
        {"l_orderkey", "o_orderdate", "o_shippriority", "l_extendedprice",
         "l_discount"},
}


@pytest.mark.parametrize("query,keys", sorted(EMITTED))
def test_tpch_joins_carry_what_their_parent_reads(tpch_ctx, query, keys):
    opt = optimize(tpch_ctx.sql(_query(query)).plan)
    by_keys = {"=".join(sorted(j.on[0])): j for j in _joins(opt)}
    join = by_keys[keys]
    assert set(join.columns) == EMITTED[query, keys]
    assert join.schema().names() == join.columns
    # the INPUTS keep the keys (and a pushed-down filter's column)
    for side, key in zip((join.left, join.right), join.on[0]):
        assert key in side.schema().names()
    # ... and the physical join says the same, in the plan's own text
    from ballista_tpu.physical.planner import create_physical_plan

    assert f"out=[{', '.join(join.columns)}]" in \
        create_physical_plan(opt).pretty()


def _two_tables():
    a = MemTableSource.from_pydict(
        schema(("ak", Int64), ("x", Int64), ("f", Int64)),
        {"ak": np.arange(6), "x": np.arange(6) * 2, "f": np.arange(6) % 2})
    b = MemTableSource.from_pydict(
        schema(("bk", Int64), ("y", Int64)),
        {"bk": np.arange(6), "y": np.arange(6) * 3})
    return LogicalPlanBuilder.scan("a", a), LogicalPlanBuilder.scan("b", b)


def test_select_star_over_a_join_carries_no_list():
    a, b = _two_tables()
    (join,) = _joins(optimize(a.join(b, [("ak", "bk")]).build()))
    assert join.columns is None
    assert join.schema().names() == ("ak", "x", "f", "bk", "y")


def test_filter_above_a_join_keeps_its_column():
    """``f + y > 3`` reads both sides, so it stays ABOVE the join: the join
    emits ``f`` and ``y`` for it though the projection reads only ``x``."""
    a, b = _two_tables()
    plan = (a.join(b, [("ak", "bk")])
            .filter(col("f") + col("y") > lit(3))
            .project([col("x")]).build())
    (join,) = _joins(optimize(plan))
    assert join.columns == ("x", "f", "y")


def test_semi_join_takes_no_list_and_optimizing_twice_changes_nothing():
    a, b = _two_tables()
    semi = a.join(b, [("ak", "bk")], how="semi").project([col("x")]).build()
    (join,) = _joins(optimize(semi))
    assert join.columns is None
    inner = a.join(b, [("ak", "bk")]).project([col("y")]).build()
    once = optimize(inner)
    assert _joins(once)[0].columns == ("y",)
    assert optimize(once).pretty() == once.pretty()


@pytest.mark.parametrize("key_at", ["first", "second"])
def test_count_star_over_a_join_keeps_a_column_its_inputs_emit(key_at,
                                                              tmp_path):
    """Nothing above reads the join, so it keeps ONE column to carry the
    rows: one the pruned left input still emits, wherever the key stands
    in the table (the inputs are pruned to the keys)."""
    from ballista_tpu import count, serde
    from ballista_tpu.execution import collect, collect_physical, \
        plan_logical
    from ballista_tpu.io import TblSource
    from ballista_tpu.logical import TableScan

    # file-backed scans: a memory table does not cross the wire
    (tmp_path / "l.tbl").write_text(
        "".join(f"{i}|{i * 2}|\n" for i in range(6)))
    (tmp_path / "r.tbl").write_text(
        "".join(f"{i % 3}|{i}|\n" for i in range(6)))
    lcols = (("lk", Int64), ("lv", Int64))
    l = TblSource(str(tmp_path / "l.tbl"),
                  schema(*(lcols if key_at == "first" else lcols[::-1])))
    r = TblSource(str(tmp_path / "r.tbl"),
                  schema(("rk", Int64), ("rv", Int64)))
    key = "lk" if key_at == "first" else "lv"  # the table's second column
    plan = (LogicalPlanBuilder(TableScan("l", l))
            .join(LogicalPlanBuilder(TableScan("r", r)), [(key, "rk")])
            .aggregate([], [count()]).build())
    (join,) = _joins(optimize(plan))
    assert join.columns == (key,)
    assert join.schema().names() == (key,)
    assert int(collect(plan).iloc[0, 0]) == 6
    phys = plan_logical(plan)
    back = serde.physical_from_proto(serde.physical_to_proto(phys))
    (sent,), (got,) = _joins(phys), _joins(back)
    assert got.out_columns == sent.out_columns == (key,)
    assert got.output_schema() == sent.output_schema()
    assert int(list(collect_physical(back).values())[0][0]) == 6


# -- (b) answers do not depend on the list ----------------------------------


def _nullable(source, column, every):
    """``source`` with every ``every``-th row of ``column`` NULL."""
    parts, seen = [], 0
    at = source.table_schema().index_of(column)
    for batches in source._partitions:
        out = []
        for b in batches:
            n = b.num_rows_host()
            validity = np.zeros(b.capacity, bool)
            validity[:n] = (np.arange(seen, seen + n) % every) != 0
            seen += n
            c = b.columns[at]
            cols = list(b.columns)
            cols[at] = Column(c.values, c.dtype, jnp.asarray(validity),
                              c.dictionary)
            out.append(ColumnBatch(b.schema, cols, b.selection, b.num_rows))
        parts.append(out)
    return MemTableSource(source.table_schema(), parts)


def _sides(unique: bool):
    """Left ``l`` (90 rows, 3 partitions) and right ``r`` (40 rows, 2
    partitions), each with a utf8 and a nullable column; keys miss on both
    sides, and ``r``'s repeat unless ``unique``."""
    rng = np.random.default_rng(5)
    lk = rng.integers(3, 30, 90)
    rk = np.arange(40) if unique else rng.integers(0, 20, 40)
    left = MemTableSource.from_pydict(
        schema(("lk", Int64), ("ls", Utf8), ("ln", Int64), ("lv", Int64)),
        {"lk": lk, "ls": [f"s{i % 7}" for i in range(90)],
         "ln": np.arange(90) * 5, "lv": np.arange(90)}, num_partitions=3)
    right = MemTableSource.from_pydict(
        schema(("rk", Int64), ("rs", Utf8), ("rn", Int64), ("rv", Int64)),
        {"rk": rk, "rs": [f"t{i % 5}" for i in range(40)],
         "rn": np.arange(40) * 7, "rv": np.arange(40) + 1000},
        num_partitions=2)
    return _nullable(left, "ln", 4), _nullable(right, "rn", 3)


def _collect(logical, partitioned: bool):
    from ballista_tpu.execution import collect_physical
    from ballista_tpu.physical.fusion import maybe_fuse
    from ballista_tpu.physical.planner import (PlannerOptions,
                                               create_physical_plan)

    opts = PlannerOptions(
        join_partition_threshold=1 if partitioned else None,
        join_partitions=4)
    phys = maybe_fuse(create_physical_plan(logical, opts))
    return _sorted(pd.DataFrame(collect_physical(phys))), phys


def _sorted(frame):
    """Rows in one order, missing values in one representation."""
    frame = frame.sort_values(list(frame.columns)).reset_index(drop=True)
    return frame.astype(object).where(pd.notna(frame), None)


def _without_lists(plan):
    """``plan`` with every join emitting everything, as before the list."""
    updates = {f.name: _without_lists(v) for f in dataclasses.fields(plan)
               if hasattr(v := getattr(plan, f.name), "children")}
    if isinstance(plan, Join):
        updates["columns"] = None
    return dataclasses.replace(plan, **updates) if updates else plan


# what the parent reads: a utf8 and a nullable column among the kept in
# one, among the dropped in the other
KEPT = {"strings_and_nulls_kept": ["ls", "rn", "lv"],
        "strings_and_nulls_dropped": ["lv", "rv"]}


@pytest.mark.parametrize("kept", sorted(KEPT))
@pytest.mark.parametrize("partitioned", [False, True],
                         ids=["merged", "partitioned"])
@pytest.mark.parametrize("unique", [True, False],
                         ids=["unique", "expanding"])
@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_answers_equal_with_and_without_the_list(how, unique, partitioned,
                                                 kept):
    left, right = _sides(unique)
    plan = (LogicalPlanBuilder.scan("l", left)
            .join(LogicalPlanBuilder.scan("r", right), [("lk", "rk")],
                  how=how)
            .project([col(c) for c in KEPT[kept]]).build())
    opt = optimize(plan)
    (join,) = _joins(opt)
    assert set(join.columns) == set(KEPT[kept])
    got, phys = _collect(opt, partitioned)
    want, full = _collect(_without_lists(opt), partitioned)
    (pruned,), (whole,) = _joins(phys), _joins(full)
    assert set(pruned.output_schema().names()) == set(KEPT[kept])
    # without the list the join emits its inputs whole: the scans' pruned
    # columns, the keys among them
    assert set(whole.output_schema().names()) == \
        set(KEPT[kept]) | {"lk", "rk"}
    assert pruned.partitioned == whole.partitioned
    assert len(got) > 0
    pd.testing.assert_frame_equal(got, want, check_dtype=False)
    # ... and with pandas, on the columns that hold no NULL of their own
    if kept == "strings_and_nulls_dropped":
        ldf = pd.DataFrame({"lk": _column(left, "lk"),
                            "lv": _column(left, "lv")})
        rdf = pd.DataFrame({"rk": _column(right, "rk"),
                            "rv": _column(right, "rv")})
        exp = ldf.merge(rdf, how={"full": "outer"}.get(how, how),
                        left_on="lk", right_on="rk")[["lv", "rv"]]
        exp = _sorted(exp)
        assert len(exp) == len(got)
        for c in ("lv", "rv"):
            assert [None if v is None else int(v) for v in exp[c]] == \
                [None if v is None else int(v) for v in got[c]], c


def _column(source, name):
    return np.concatenate([np.asarray(b.to_pydict()[name])
                           for part in source._partitions for b in part])


# -- (c) the list is part of a join's compiled identity ---------------------


def _scans():
    left, right = _sides(unique=False)
    return ScanExec("r", right), ScanExec("l", left)


def test_joins_that_emit_different_columns_never_share_a_program():
    build, probe = _scans()
    on = [("rk", "lk")]
    whole = JoinExec(build, probe, on)
    some = JoinExec(build, probe, on, out_columns=("lv", "rv"))
    other = JoinExec(build, probe, on, out_columns=("lv", "rs"))
    again = JoinExec(build, probe, on, out_columns=("lv", "rv"))
    sigs = [j.compile_signature() for j in (whole, some, other)]
    assert len(set(sigs)) == 3
    assert again.compile_signature() == some.compile_signature()
    # the governed key is built from it: equal lists share the expanding
    # program, different lists each get their own
    from ballista_tpu.compile import governor

    def expansions() -> int:
        return governor().namespace_sizes().get("join.expand", 0)

    counts = [expansions()]
    for j in (some, again, other, whole):
        list(j.execute(0))
        counts.append(expansions())
    steps = [b - a for a, b in zip(counts, counts[1:])]
    assert steps[0] > 0 and steps[1] == 0 and steps[2] > 0 and steps[3] > 0
    # a list that names every column in the natural order is no list
    names = whole.output_schema().names()
    assert JoinExec(build, probe, on, out_columns=names).out_columns is None
    assert some.output_schema().names() == ("lv", "rv")
    assert "out=[lv, rv]" in some.display()
    assert "out=" not in whole.display()
    # semi/anti joins emit the probe batch and take no list
    semi = JoinExec(build, probe, on, "semi", out_columns=("lv",))
    assert semi.out_columns is None
    # everyone who rebuilds a join carries the list over
    assert some.with_new_children(some.children()).out_columns == \
        ("lv", "rv")


# -- (d) the wire -----------------------------------------------------------


def test_out_columns_round_trip_through_the_proto(tmp_path):
    from ballista_tpu import serde
    from ballista_tpu.io import TblSource
    from ballista_tpu.logical import TableScan
    from ballista_tpu.physical.mesh_agg import MeshJoinExec

    # file-backed scans: a memory table does not cross the wire
    (tmp_path / "l.tbl").write_text("1|a|10|\n")
    (tmp_path / "r.tbl").write_text("1|b|20|\n")
    l = TblSource(str(tmp_path / "l.tbl"),
                  schema(("lk", Int64), ("ls", Utf8), ("lv", Int64)))
    r = TblSource(str(tmp_path / "r.tbl"),
                  schema(("rk", Int64), ("rs", Utf8), ("rv", Int64)))
    build, probe = ScanExec("r", r), ScanExec("l", l)
    on = [("rk", "lk")]
    for plan in (JoinExec(build, probe, on, "left", partitioned=True,
                          out_columns=("lv", "rs")),
                 MeshJoinExec(build, probe, on, "inner", 4,
                              out_columns=("rv", "lv")),
                 JoinExec(build, probe, on)):
        back = serde.physical_from_proto(serde.physical_to_proto(plan))
        assert type(back) is type(plan)
        assert back.out_columns == plan.out_columns
        assert back.output_schema() == plan.output_schema()
        assert back.compile_signature() == plan.compile_signature()


def test_proto_text_and_generated_module_agree():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "dev", "check_proto_sync.py")],
        capture_output=True, text=True)
    assert done.returncode == 0, done.stdout + done.stderr


# -- (e) the scheduler's mesh fusion ----------------------------------------


def test_mesh_join_emits_what_the_join_it_replaced_emitted(eight_devices):
    from ballista_tpu.distributed.planner import DistributedPlanner
    from ballista_tpu.distributed.scheduler import _fuse_mesh_stages
    from ballista_tpu.physical.mesh_agg import MeshJoinExec
    from ballista_tpu.physical.planner import (PlannerOptions,
                                               create_physical_plan)

    left, right = _sides(unique=False)
    plan = optimize(
        LogicalPlanBuilder.scan("l", left)
        .join(LogicalPlanBuilder.scan("r", right), [("lk", "rk")])
        .project([col("rs"), col("lv"), col("ln")]).build())
    phys = create_physical_plan(
        plan, PlannerOptions(join_partition_threshold=1, join_partitions=4))
    (host,) = _joins(phys)
    assert host.partitioned and set(host.out_columns) == {"rs", "lv", "ln"}
    stages = _fuse_mesh_stages(
        DistributedPlanner().plan_query_stages("j42", phys), 4)

    def find(node):
        if isinstance(node, MeshJoinExec):
            return node
        return next((m for m in map(find, node.children()) if m), None)

    mesh = next(m for m in (find(s.child) for s in stages) if m)
    assert mesh.out_columns == host.out_columns
    assert mesh.output_schema() == host.output_schema()
    assert f"out=[{', '.join(host.out_columns)}]" in mesh.display()
    rebuilt = mesh.with_new_children(mesh.children())
    assert rebuilt.out_columns == host.out_columns
    assert rebuilt.compile_signature() == mesh.compile_signature()

    def rows(op):
        frames = [b.to_pandas() for p in range(
            op.output_partitioning().num_partitions) for b in op.execute(p)]
        return _sorted(pd.concat(frames, ignore_index=True))

    pd.testing.assert_frame_equal(rows(mesh), rows(host), check_dtype=False)


# -- (f) the events that say what was gathered ------------------------------


def test_expand_and_take_events_carry_the_columns_gathered():
    from ballista_tpu.observability.tracing import ring_records

    left, right = _sides(unique=False)
    plan = optimize(
        LogicalPlanBuilder.scan("l", left)
        .join(LogicalPlanBuilder.scan("r", right), [("lk", "rk")])
        .project([col("lv"), col("rs")]).build())
    started = time.time()
    _, phys = _collect(plan, partitioned=True)
    (join,) = _joins(phys)
    seen = {}
    for r in ring_records(since=started):
        seen.setdefault(r.get("name"), []).append(r)
    # the expanding probe gathers the two columns the projection reads
    assert seen["join.expand"]
    assert {r["cols"] for r in seen["join.expand"]} == {2}
    # the repartitions below the join move their inputs whole: the join's
    # INPUTS keep the keys (scans pruned to key + what is read)
    assert {r["cols"] for r in seen["repart.take"]} == {2, 2}
    assert all(r["out"] == 2 for r in seen["join.build"])
    assert "out=[lv, rs]" in join.display()
