"""TPC-H integration tests: SQL text -> engine results vs pandas oracle.

The engine-level equivalent of the reference's docker-compose TPC-H
integration run (reference: dev/integration-tests.sh:1-11, query set
q1,q3,q5,q6,q10,q12 from rust/benchmarks/tpch/run.sh:6-9) — but with
programmatic golden assertions instead of eyeballing."""

import os

import pytest

from benchmarks.tpch import datagen, oracle
from benchmarks.tpch.schema_def import register_tpch

# the reference's integration set is q1,q3,q5,q6,q10,q12
# (rust/benchmarks/tpch/run.sh:6-9); we assert a much wider set
QUERIES = ["q1", "q2", "q3", "q4", "q5", "q6", "q7", "q8", "q9", "q10",
           "q11", "q12", "q13", "q14", "q15", "q16", "q17", "q18", "q19",
           "q20", "q21", "q22"]
QDIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "tpch", "queries")


@pytest.fixture(scope="session")
def tpch(tmp_path_factory):
    data_dir = str(tmp_path_factory.mktemp("tpch_data"))
    datagen.generate(data_dir, scale=0.002, num_parts=2)
    from ballista_tpu.client import BallistaContext

    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    tables = oracle.load_tables(data_dir)
    return ctx, tables


@pytest.mark.parametrize("qname", QUERIES)
def test_tpch_query(tpch, qname):
    ctx, tables = tpch
    sql = open(os.path.join(QDIR, f"{qname}.sql")).read()
    oracle.assert_frames_match(qname, ctx.sql(sql).collect(),
                               oracle.ORACLES[qname](tables))
