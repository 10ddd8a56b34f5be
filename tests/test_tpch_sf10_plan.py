"""The plan ``tpch-sf10-standalone`` settles on, at SF0.05 on the CPU: q3
and q14 through ``BallistaContext.standalone`` with explicit settings that
scale the planner's and the adaptive pass's thresholds down with the data,
so that the tree is the one SF10 gets on the chip (PERF.md section 6, "HEAD
at SF10"): both of q3's joins partitioned, the first through coalesced
reads; q14 built on the FILTERED lineitem after the adaptive pass demoted
its partitioned join, probed from ``part`` by the expanding probe. Four
executions each equal the benchmark's plain reference under the queries' own
limits, and the spans say what a cached plan builds once and then keeps:
which side a join built, that later executions reuse it, how many rows a
repartition moved, and which tables a warm query still scans."""

import os
import sys
import time
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXECUTIONS = 4
# SF0.05 is 1/200 of SF10: the planner's row threshold and the adaptive
# pass's byte thresholds (defaults 1,000,000 rows, 32 MiB, 64 MiB) by that
SETTINGS = {"join.partitioned.threshold": "1000", "join.partitions": "8",
            "adaptive.broadcast_threshold_bytes": "300000",
            "adaptive.target_partition_bytes": "335544"}
NAMES = ("adaptive.rule", "join.build", "join.build_reused",
         "repart.materialize", "repart.take", "repart.reused", "scan.serve",
         "join.expand")


@pytest.fixture(scope="module")
def bench():
    """perfbench's modules (found by name from its own directory)."""
    added = [os.path.join(ROOT, "perfbench")]
    sys.path[:0] = added
    import datagen
    import mesh_bytes
    import reference
    import run

    yield {"datagen": datagen, "mesh_bytes": mesh_bytes,
           "reference": reference, "run": run}
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def cell(bench):
    return bench["run"].find_cell("standalone-join-sf10")


@pytest.fixture(scope="module")
def data_dir(bench, cell, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("sf0.05"))
    tables = sorted({t for spec in cell["queries"].values()
                     for t in spec["reads"]})
    bench["datagen"].generate(out, 0.05, tables,
                              int(cell["config"]["files_per_table"]), 41)
    return out


def context(bench, cell, data_dir):
    from ballista_tpu.client import BallistaContext

    ctx = BallistaContext.standalone(**SETTINGS)
    for name in {t for spec in cell["queries"].values()
                 for t in spec["reads"]}:
        table = bench["datagen"].table(name)
        ctx.register_parquet(name, os.path.join(data_dir, name),
                             table.program_schema(),
                             primary_key=table.PRIMARY_KEY)
    return ctx


def executions(bench, cell, data_dir, query):
    """Per execution of ``query`` on a context of its own: the new spans'
    ring records by name, after the answer was held to the reference."""
    from ballista_tpu.observability.tracing import ring_records

    ctx = context(bench, cell, data_dir)
    spec = cell["queries"][query]
    want = bench["reference"].query(query)(data_dir)
    out = []
    for execution in range(EXECUTIONS):
        started = time.time()
        frame = ctx.sql(spec["text"]).collect()
        got = bench["reference"].compare(frame, want,
                                         spec["quotient_columns"])
        assert all(got[k] <= spec["limits"][k] for k in spec["limits"]), \
            (execution, got)
        by_name = {name: [] for name in NAMES}
        for r in ring_records(since=started):
            if r.get("name") in by_name:
                by_name[r["name"]].append(r)
        out.append(by_name)
    return out, ctx._last_query_phys.pretty()


def rows(records) -> int:
    return sum(int(r["rows"]) for r in records)


def test_q14_builds_on_the_filtered_lineitem_once(bench, cell, data_dir):
    runs, plan = executions(bench, cell, data_dir, "q14")
    assert "[adaptive: broadcast build" in plan
    # the rewrite is named once, in the execution that made it
    rules = [r for run in runs for r in run["adaptive.rule"]]
    assert [r["rule"] for r in rules] == ["broadcast_build"]
    assert len(runs[0]["adaptive.rule"]) == 1
    assert 0 < rules[0]["bytes"] < rules[0]["threshold"] == 300000
    assert (rules[0]["from"], rules[0]["to"]) == (8, 1)
    # the build is the month of lineitem, concatenated from 8 pieces
    ref = bench["reference"]
    ship = ref.load(data_dir, "lineitem", ["l_shipdate"])["l_shipdate"]
    month = int(((ship >= ref.D("1995-09-01"))
                 & (ship < ref.D("1995-10-01"))).sum())
    (build,) = runs[0]["join.build"]
    assert (build["side"], build["rows"], build["pieces"]) == \
        ("lineitem", month, 8)
    assert not build["unique"] and not build["partitioned"]
    assert build["dur"] > 0 and build["capacity"] >= month
    # ... out of a repartition whose takes add up to the filter's output
    (made,) = runs[0]["repart.materialize"]
    assert (made["sources"], made["rows"]) == (4, month)
    assert rows(runs[0]["repart.take"]) == month
    assert len(runs[0]["repart.take"]) == 8
    parts = bench["datagen"].table("part").rows(0.05)
    for run in runs[1:]:
        # kept by the cached plan: nothing of the lineitem side runs again
        assert not run["join.build"] and not run["repart.materialize"]
        assert not run["repart.take"] and not run["adaptive.rule"]
        assert len(run["join.build_reused"]) == 4  # one a probe partition
        served = Counter(r["table"] for r in run["scan.serve"])
        assert served == {"part": 4}
        assert rows(run["scan.serve"]) == parts
        assert all(r["how"] == "resident" for r in run["scan.serve"])
        # duplicate build keys: the probe from part is the expanding one
        assert len(run["join.expand"]) == 4
    scanned = Counter(r["table"] for r in runs[0]["scan.serve"])
    assert scanned == {"part": 4, "lineitem": 4}


def test_q3_partitions_both_joins_and_keeps_what_it_built(bench, cell,
                                                          data_dir):
    runs, plan = executions(bench, cell, data_dir, "q3")
    assert plan.count("partitioned") == 2
    assert plan.count("AdaptiveShuffleReadExec [adaptive: coalesced 8→") == 2
    rules = [r for run in runs for r in run["adaptive.rule"]]
    assert [r["rule"] for r in rules] == ["coalesce"]
    assert rules[0]["from"] == 8 and 1 < rules[0]["to"] < 8
    assert rules[0]["where"] == "standalone"
    first = runs[0]
    # four repartitions: the filtered orders and customers, the filtered
    # lineitem, and the first join's output; each one's takes move
    # exactly the rows it sorted
    mesh = bench["mesh_bytes"]
    made = sorted(r["rows"] for r in first["repart.materialize"])
    assert len(made) == 4
    sides = {s["side"]: s["rows"] for s in mesh.sides("q3", data_dir)}
    assert sides["lineitem"] in made and sides["orders"] in made
    assert rows(first["repart.take"]) == sum(made)
    built = Counter(r["side"] for r in first["join.build"])
    assert built["lineitem"] == 8 and built["orders"] == rules[0]["to"]
    assert all(r["partitioned"] for r in first["join.build"])
    assert sum(r["rows"] for r in first["join.build"]
               if r["side"] == "lineitem") == sides["lineitem"]
    for run in runs[1:]:
        # a warm q3 runs neither scan nor build: it gathers the first
        # join's kept output into the second join's partitions and probes
        assert not run["join.build"] and not run["repart.materialize"]
        assert not run["scan.serve"] and not run["adaptive.rule"]
        assert len(run["join.build_reused"]) == 8
        assert len(run["repart.take"]) == 8
        assert rows(run["repart.take"]) == sides["orders"]


def test_the_cell_resolves_to_one_chip_at_sf10(bench, cell):
    assert cell["chips"] == cell["config"]["chips"] == 1
    assert cell["config"]["scale"] == 10.0
    assert cell["config"]["mode"] == "standalone"
    assert cell["traffic"]["round"] == ["q3", "q14"]
    sf3 = bench["run"].find_cell("standalone-join")["config"]
    # the SF3 deployment at another scale: same files a table, same
    # residency budget, the same two guarantees first
    for key in ("files_per_table", "environment", "engine_defaults_kept",
                "warm_rounds_max", "devices"):
        assert cell["config"][key] == sf3[key], key
    assert cell["config"]["guarantees"][:2] == sf3["guarantees"]
