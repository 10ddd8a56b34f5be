"""The deployment ``tpch-mesh4-served`` at SF0.05 on four of conftest's CPU
devices: q3 and q14 through ``LocalCluster(1 executor, 2 slots, 4
devices)`` and a remote client whose explicit settings make the joins
partitioned, four executions each. Every answer equals the benchmark's
plain reference under the queries' own limits, every execution exchanges
its joins on the mesh (the cost feedback, which at this size turns a
partitioned join into a broadcast join after the first run, never
overrides an explicit client setting), and the rows that crossed the mesh
did not go through shuffle files."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXECUTIONS = 4


@pytest.fixture(scope="module")
def bench():
    """perfbench's modules (found by name from its own directory)."""
    added = [os.path.join(ROOT, "perfbench")]
    sys.path[:0] = added
    import datagen
    import engine
    import mesh_bytes
    import reference
    import run

    yield {"datagen": datagen, "engine": engine, "mesh_bytes": mesh_bytes,
           "reference": reference, "run": run}
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    cell = bench["run"].find_cell("mesh4-join")
    data_dir = str(tmp_path_factory.mktemp("sf0.05"))
    tables = sorted({t for spec in cell["queries"].values()
                     for t in spec["reads"]})
    bench["datagen"].generate(data_dir, 0.05, tables,
                              int(cell["config"]["files_per_table"]), 37)
    config = dict(cell["config"], client_settings={
        **cell["config"]["client_settings"],
        "join.partitioned.threshold": "1", "join.partitions": "8"})
    assert (config["executors"], config["slots"], config["devices"]) == \
        (1, 2, 4)
    eng = bench["engine"].Engine(config, data_dir, tables)
    try:
        yield cell, data_dir, eng.context()
    finally:
        eng.close()


@pytest.mark.parametrize("query", ["q3", "q14"])
def test_every_execution_exchanges_and_equals_the_reference(bench, served,
                                                            query):
    import time

    from ballista_tpu.observability.tracing import ring_records, span_totals
    from ballista_tpu.physical import mesh_input

    cell, data_dir, ctx = served
    spec = cell["queries"][query]
    want = bench["reference"].query(query)(data_dir)
    for execution in range(EXECUTIONS):
        before = mesh_input.STATS["exchanges"]
        fused = span_totals().get("mesh.fused", {}).get("count", 0)
        started = time.time()
        frame = ctx.sql(spec["text"]).collect()
        got = bench["reference"].compare(frame, want,
                                         spec["quotient_columns"])
        assert all(got[k] <= spec["limits"][k] for k in spec["limits"]), \
            (execution, got)
        events = [r for r in ring_records(since=started)
                  if r.get("name") == "mesh.exchange"]
        # one event a side exchanged: the counter and the ring agree
        assert len(events) == mesh_input.STATS["exchanges"] - before
        assert len(events) >= 2, f"execution {execution}: no mesh exchange"
        assert span_totals()["mesh.fused"]["count"] > fused
        assert all(e["n_dev"] == 4 and 0 < e["rows"] <= e["slots"]
                   for e in events)
        # the fused joins' rows crossed the mesh, not the data plane
        record = bench["engine"].query_record(ctx)
        crossed = sum(e["bytes"] for e in events)
        assert record["shuffle_bytes"] < 0.05 * crossed, \
            (record["shuffle_bytes"], crossed)
        if query == "q3":
            # the orders-lineitem join's sides are the rows that pass
            # the query's filters, as the benchmark's bytes model counts
            sides = {e["side"]: e["rows"] for e in events[-2:]}
            assert sum(sides.values()) == \
                bench["mesh_bytes"].rows_exchanged("q3", data_dir)
    totals = span_totals()
    assert totals["mesh.assemble"]["count"] >= 2 * EXECUTIONS
    assert totals["mesh.assemble"]["seconds"] > 0
