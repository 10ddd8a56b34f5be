"""TPC-H at SF=0.2 (~1.2M lineitem rows) through BOTH the standalone
engine and the distributed LocalCluster, asserted against pandas oracles.

Opt-in (``pytest -m sf02``): the CI-scale suite (test_tpch.py, SF=0.002)
never exercises capacity-overflow/retry paths or the distributed shuffle
under realistic data sizes — this one does. Round-1 lesson: bugs appear
only at scale (q7's OR-collapse showed up first at SF0.05).
"""

import os

import pytest

from benchmarks.tpch import datagen, oracle
from benchmarks.tpch.schema_def import register_tpch

QUERIES = [f"q{i}" for i in range(1, 23)]
QDIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "tpch",
                    "queries")

pytestmark = pytest.mark.sf02


@pytest.fixture(scope="session")
def sf02_data(tmp_path_factory):
    # reuse the bench dataset when present (same generator + seed)
    prebuilt = os.path.join(os.path.dirname(__file__), "..", "bench_data",
                            "sf02")
    if os.path.exists(os.path.join(prebuilt, "lineitem")):
        data_dir = prebuilt
    else:
        data_dir = str(tmp_path_factory.mktemp("tpch_sf02"))
        datagen.generate(data_dir, scale=0.2, num_parts=2)
    return data_dir, oracle.load_tables(data_dir)


@pytest.fixture(scope="session")
def sf02_standalone(sf02_data):
    from ballista_tpu.client import BallistaContext

    data_dir, tables = sf02_data
    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl", cached=True)
    return ctx, tables


@pytest.fixture(scope="session")
def sf02_cluster(sf02_data):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.distributed.executor import LocalCluster

    data_dir, tables = sf02_data
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    ctx = BallistaContext.remote("localhost", cluster.port)
    register_tpch(ctx, data_dir, "tbl")
    yield ctx, tables
    cluster.shutdown()


def _assert_matches(got, exp, qname):
    oracle.assert_frames_match(qname, got, exp)


@pytest.mark.parametrize("qname", QUERIES)
def test_sf02_standalone(sf02_standalone, qname):
    ctx, tables = sf02_standalone
    sql = open(os.path.join(QDIR, f"{qname}.sql")).read()
    _assert_matches(ctx.sql(sql).collect(), oracle.ORACLES[qname](tables),
                    qname)


@pytest.mark.parametrize("qname", QUERIES)
def test_sf02_cluster(sf02_cluster, qname):
    ctx, tables = sf02_cluster
    sql = open(os.path.join(QDIR, f"{qname}.sql")).read()
    _assert_matches(ctx.sql(sql).collect(), oracle.ORACLES[qname](tables),
                    qname)
