"""EXPLAIN end-to-end: SQL text -> plan rows, both execution modes.

Reference surface: rust/core/proto/ballista.proto:232 ExplainNode (the
reference serializes DataFusion's SQL EXPLAIN); here EXPLAIN renders at
physical-planning time and the rows execute as a normal leaf operator, so
the distributed path needs no special result channel.
"""

import numpy as np
import pytest

from ballista_tpu import schema, Int64, Utf8
from ballista_tpu.client import BallistaContext
from ballista_tpu.io import TblSource
from ballista_tpu import serde
from ballista_tpu import logical as lp
from ballista_tpu.physical.explain import ExplainExec


def _make_ctx(tmp_path):
    p = tmp_path / "t.tbl"
    p.write_text("".join(f"{i}|k{i % 3}|\n" for i in range(50)))
    ctx = BallistaContext.standalone()
    ctx.register_source("t", TblSource(str(p), schema(("a", Int64),
                                                      ("c", Utf8))))
    return ctx


def test_explain_standalone(tmp_path):
    ctx = _make_ctx(tmp_path)
    out = ctx.sql(
        "EXPLAIN SELECT c, sum(a) FROM t WHERE a > 5 GROUP BY c"
    ).collect()
    assert list(out.columns) == ["plan_type", "plan"]
    types = out["plan_type"].tolist()
    assert types == ["logical_plan", "physical_plan"]
    logical_text = out["plan"][0]
    assert "Aggregate" in logical_text and "TableScan" in logical_text
    assert "HashAggregateExec" in out["plan"][1]


def test_explain_verbose_shows_preoptimization_plan(tmp_path):
    ctx = _make_ctx(tmp_path)
    out = ctx.sql("EXPLAIN VERBOSE SELECT a FROM t WHERE a > 5").collect()
    types = out["plan_type"].tolist()
    assert types[0] == "initial_logical_plan"
    assert "logical_plan" in types and "physical_plan" in types


def test_explain_schema_and_df_api(tmp_path):
    ctx = _make_ctx(tmp_path)
    df = ctx.sql("EXPLAIN SELECT a FROM t")
    names = list(df.schema().names())
    assert names == ["plan_type", "plan"]


def test_verbose_is_soft_keyword(tmp_path):
    """A column named ``verbose`` (or ``explain``) must keep working —
    the words are contextual keywords, special only at statement start."""
    p = tmp_path / "v.tbl"
    p.write_text("".join(f"{i}|{i * 2}|\n" for i in range(10)))
    ctx = BallistaContext.standalone()
    ctx.register_source("v", TblSource(str(p), schema(("verbose", Int64),
                                                      ("explain", Int64))))
    out = ctx.sql(
        "SELECT verbose, explain FROM v WHERE verbose > 3 ORDER BY verbose"
    ).collect()
    assert out["verbose"].tolist() == [4, 5, 6, 7, 8, 9]
    assert out["explain"].tolist() == [8, 10, 12, 14, 16, 18]


def test_explain_logical_serde_roundtrip(tmp_path):
    ctx = _make_ctx(tmp_path)
    df = ctx.sql("EXPLAIN VERBOSE SELECT a FROM t")
    plan = df.plan
    assert isinstance(plan, lp.Explain) and plan.verbose
    rt = serde.plan_from_proto(serde.plan_to_proto(plan))
    assert isinstance(rt, lp.Explain)
    assert rt.verbose is True
    assert list(rt.schema().names()) == ["plan_type", "plan"]
    assert rt.input.schema().names() == plan.input.schema().names()


def test_explain_physical_serde_roundtrip():
    node = ExplainExec([("logical_plan", "Scan: t\n"),
                        ("physical_plan", "ScanExec: t\n")])
    rt = serde.physical_from_proto(serde.physical_to_proto(node))
    assert isinstance(rt, ExplainExec)
    assert rt.rows == node.rows
    got = list(rt.execute(0))[0].to_pydict()
    assert got["plan_type"].tolist() == ["logical_plan", "physical_plan"]


def test_explain_through_cluster(tmp_path):
    """Server-planned EXPLAIN: SQL travels to the scheduler, the rendered
    rows come back over the standard distributed fetch path."""
    from ballista_tpu.distributed.executor import LocalCluster

    p = tmp_path / "t.tbl"
    p.write_text("".join(f"{i}|k{i % 3}|\n" for i in range(50)))
    src = TblSource(str(p), schema(("a", Int64), ("c", Utf8)))
    cluster = LocalCluster(num_executors=1, concurrent_tasks=1)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port,
                                     **{"plan.server": "on"})
        ctx.register_source("t", src)
        out = ctx.sql("EXPLAIN SELECT c, sum(a) FROM t GROUP BY c").collect()
        assert out["plan_type"].tolist() == ["logical_plan", "physical_plan"]
        assert "Aggregate" in out["plan"][0]
    finally:
        cluster.shutdown()


def test_array_scalar_function(tmp_path):
    """ARRAY constructor (reference: rust/core/proto/ballista.proto:105):
    rectangular fixed-size-list column, collectable to per-row vectors."""
    p = tmp_path / "n.tbl"
    p.write_text("".join(f"{i}|{i * 10}|\n" for i in range(5)))
    ctx = BallistaContext.standalone()
    ctx.register_source("n", TblSource(str(p), schema(("x", Int64),
                                                      ("y", Int64))))
    out = ctx.sql("SELECT array(x, y) AS v FROM n").collect()
    assert len(out) == 5
    row0 = out["v"].iloc[0]
    np.testing.assert_array_equal(np.asarray(row0, dtype=np.int64), [0, 0])
    row3 = out["v"].iloc[3]
    np.testing.assert_array_equal(np.asarray(row3, dtype=np.int64), [3, 30])


def test_array_crosses_stage_boundary(tmp_path):
    """List column through an intermediate shuffle stage (ORDER BY forces
    a merge stage, so the array travels via IPC shuffle files and is
    rebuilt by batches_from_pieces — the 2-D placement path)."""
    from ballista_tpu.distributed.executor import LocalCluster

    p = tmp_path / "n.tbl"
    p.write_text("".join(f"{i}|{i * 10}|\n" for i in range(16)))
    src = TblSource(str(p), schema(("x", Int64), ("y", Int64)))
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port)
        ctx.register_source("n", src)
        out = ctx.sql(
            "SELECT x, array(x, y) AS v FROM n ORDER BY x DESC LIMIT 5"
        ).collect()
        assert out["x"].tolist() == [15, 14, 13, 12, 11]
        for i, xv in enumerate(out["x"].tolist()):
            np.testing.assert_array_equal(
                np.asarray(out["v"].iloc[i], dtype=np.int64), [xv, xv * 10])
    finally:
        cluster.shutdown()


def test_array_dtype_serde_roundtrip():
    from ballista_tpu.datatypes import FixedSizeList, Int64 as I64, Decimal

    for dt in (FixedSizeList(I64, 3), FixedSizeList(Decimal(2), 2)):
        rt = serde.dtype_from_proto(serde.dtype_to_proto(dt))
        assert rt == dt, (rt, dt)
        assert rt.element == dt.element and rt.length == dt.length


def test_array_through_cluster(tmp_path):
    """array() results cross the distributed result path: the fixed-size
    list column is written as a real Arrow FixedSizeListArray and
    reconstructed client-side."""
    from ballista_tpu.distributed.executor import LocalCluster

    p = tmp_path / "n.tbl"
    p.write_text("".join(f"{i}|{i * 10}|\n" for i in range(8)))
    src = TblSource(str(p), schema(("x", Int64), ("y", Int64)))
    cluster = LocalCluster(num_executors=1, concurrent_tasks=1)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port)
        ctx.register_source("n", src)
        out = ctx.sql("SELECT x, array(x, y) AS v FROM n").collect()
        assert len(out) == 8
        out = out.sort_values("x").reset_index(drop=True)
        for i in range(8):
            np.testing.assert_array_equal(
                np.asarray(out["v"].iloc[i], dtype=np.int64), [i, i * 10])
    finally:
        cluster.shutdown()
