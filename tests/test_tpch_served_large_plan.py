"""The plan ``tpch-large-served`` settles on, at SF0.05 on the CPU: q3 and
q14 through ``LocalCluster(2 executors, 2 slots)`` and a remote client,
with the byte targets of the cost feedback and of cluster-side adaptive
execution and the planner's row threshold scaled down with the data (SF0.05
is 1/200 of SF10), so that the control plane takes the branch it takes at
SF10 on the chip (PERF.md section 6, "HEAD served at SF10"): after the first
q3 the feedback raises ``join.partitions`` from 8 to what the shuffled bytes
call for and lowers the join threshold, so BOTH of q3's joins run
partitioned through shuffle files; cluster-side adaptive execution then
coalesces the first join's readers. Every execution equals the
benchmark's plain reference under the queries' own limits, and the spans say
what a served query pays every time: every build made, none reused; one
``shuffle.write`` a shuffling task with ``slices`` = batches x fan-out; a
blocking read of the destination vector and of every column for each batch,
whatever the fan-out; every scan
served from the device; and the finished jobs' files gone once the client
has their results."""

import math
import os
import sys
import time
from collections import Counter

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# q3: the first plans at the defaults, the second takes the feedback, the
# third shows the plan has settled (a q3 at the raised fan-out is 9 s on
# the CPU); q14's feedback moves twice, so its fourth is the settled one
EXECUTIONS = {"q3": 3, "q14": 4}
# SF0.05 is 1/200 of SF10: the feedback's and the adaptive pass's byte
# targets (64 MiB both, and 32 MiB under which a built side is broadcast)
# and the planner's row threshold (1,000,000) by that
TARGET = 64 * 1024 * 1024 // 200
THRESHOLD = 1_000_000 // 200
SETTINGS = {"controlplane.cost_target_partition_bytes": str(TARGET),
            "adaptive.target_partition_bytes": str(TARGET),
            "adaptive.broadcast_threshold_bytes": str(TARGET // 2)}
NAMES = ("controlplane.costs", "adaptive.rule", "join.build",
         "join.build_reused", "shuffle.write", "shuffle.read", "scan.serve",
         "executor.report_waited", "dataplane.release", "device.block")
READ = "device.block:ipc.batch_to_arrow"


@pytest.fixture(scope="module")
def bench():
    """perfbench's modules (found by name from its own directory)."""
    added = [os.path.join(ROOT, "perfbench")]
    sys.path[:0] = added
    import datagen
    import engine
    import reference
    import run

    yield {"datagen": datagen, "engine": engine, "reference": reference,
           "run": run}
    for p in added:
        sys.path.remove(p)


@pytest.fixture(scope="module")
def served(bench, tmp_path_factory):
    """The deployment, and per query its executions: the answer's gaps to
    the reference, the new spans' records, the span totals' deltas and the
    stages' operator rows."""
    from ballista_tpu.observability import tracing
    from ballista_tpu.physical.planner import PlannerOptions

    cell = bench["run"].find_cell("served-join-large")
    config = cell["config"]
    assert (config["executors"], config["slots"], config["devices"],
            config["scale"]) == (2, 2, 1, 10.0)
    data_dir = str(tmp_path_factory.mktemp("sf0.05"))
    tables = sorted({t for spec in cell["queries"].values()
                     for t in spec["reads"]})
    bench["datagen"].generate(data_dir, 0.05, tables,
                              int(config["files_per_table"]), 43)
    mp = pytest.MonkeyPatch()
    # the planner's DEFAULT threshold, scaled: a client setting would keep
    # the feedback from moving it, and its move is what is under test
    from_settings = PlannerOptions.from_settings

    def scaled(settings):
        opts = from_settings(settings)
        if "join.partitioned.threshold" not in (settings or {}):
            opts.join_partition_threshold = THRESHOLD
        return opts

    mp.setattr(PlannerOptions, "from_settings", staticmethod(scaled))
    for key, value in config["environment"].items():
        mp.setenv(key, value)
    # a q3 here makes some 7,000 blocked reads: a ring that holds them all
    mp.setenv("BALLISTA_FLIGHT_RECORDER_SPANS", "400000")
    tracing.reconfigure()
    eng = bench["engine"].Engine(
        dict(config, client_settings={**config["client_settings"],
                                      **SETTINGS}), data_dir, tables)
    runs = {q: [] for q in cell["queries"]}
    want = {q: bench["reference"].query(q)(data_dir)
            for q in cell["queries"]}
    try:
        ctx = eng.context()
        for execution in range(max(EXECUTIONS.values())):
            for q, spec in cell["queries"].items():
                if execution >= EXECUTIONS[q]:
                    continue
                before, started = tracing.span_totals(), time.time()
                frame = ctx.sql(spec["text"]).collect()
                after = tracing.span_totals()
                stages = dict(ctx.last_query_metrics().stages)
                runs[q].append({
                    "gaps": bench["reference"].compare(
                        frame, want[q], spec["quotient_columns"]),
                    "events": [r for r in tracing.ring_records(since=started)
                               if r.get("name") in NAMES],
                    "added": {k: t["count"] - before.get(
                        k, {"count": 0})["count"] for k, t in after.items()},
                    "stages": stages})
        work_dirs = [e.config.work_dir for e in eng.cluster.executors]
        deadline = time.time() + 10.0
        while time.time() < deadline and any(
                os.listdir(d) for d in work_dirs):
            time.sleep(0.05)
        left = {d: os.listdir(d) for d in work_dirs}
        yield cell, runs, left
    finally:
        eng.close()
        mp.undo()
        tracing.reconfigure()


def named(run, name):
    return [r for r in run["events"] if r["name"] == name]


@pytest.mark.parametrize("query", ["q3", "q14"])
def test_every_execution_equals_the_reference(served, query):
    cell, runs, _ = served
    limits = cell["queries"][query]["limits"]
    assert len(runs[query]) == EXECUTIONS[query]
    for execution, run in enumerate(runs[query]):
        assert all(run["gaps"][k] <= limits[k] for k in limits), \
            (execution, run["gaps"])


def test_the_feedback_raises_the_fan_out_and_lowers_the_threshold(served):
    _, runs, _ = served
    first, second = runs["q3"][0], runs["q3"][1]
    # the first execution plans at the defaults: eight ways, and the
    # customer side (1,500 rows after its filter) under the threshold
    assert not named(first, "controlplane.costs")
    assert {e["fan_out"] for e in named(first, "shuffle.write")} == {8}
    (note,) = named(second, "controlplane.costs")
    assert note["join_partitions_from"] == 8
    assert note["join_partitions"] == \
        math.ceil(note["shuffle_bytes"] / TARGET) > 8
    # over 8 targets shuffled: "prefer the co-partitioned join"
    assert note["shuffle_bytes"] > 8 * TARGET
    assert (note["join_threshold_from"], note["join_threshold"]) == \
        (THRESHOLD, THRESHOLD // 4)
    assert "join.partitions 8 ->" in note["notes"]
    assert {e["fan_out"] for e in named(second, "shuffle.write")} == \
        {note["join_partitions"]}


def test_q14_settles_on_the_broadcast_plan(served):
    _, runs, _ = served
    last = runs["q14"][-1]
    (note,) = named(last, "controlplane.costs")
    # little shuffled: one partition, and the threshold raised
    assert note["join_partitions"] == 1
    assert note["join_threshold"] == THRESHOLD * 4
    assert not named(last, "shuffle.write")
    builds = named(last, "join.build")
    # part, built dense in each of the four probe tasks
    assert len(builds) == 4
    assert all(not b["partitioned"] and b["mode"] == "dense"
               for b in builds)


def test_a_settled_q3_partitions_both_joins_and_coalesces_the_first(served):
    _, runs, _ = served
    for run in runs["q3"][1:]:
        (note,) = named(run, "controlplane.costs")
        fan_out = note["join_partitions"]
        (rule,) = named(run, "adaptive.rule")
        assert (rule["rule"], rule["where"], rule["from"]) == \
            ("coalesce", "cluster", fan_out)
        assert 1 < rule["to"] < fan_out and rule["bytes"] > TARGET
        builds = named(run, "join.build")
        # the coalesced customer-orders readers, then one orders-lineitem
        # task a partition: every build made from shuffle files, sorted
        assert len(builds) == rule["to"] + fan_out
        assert all(b["partitioned"] and b["mode"] == "sorted"
                   and b["side"] == "ShuffleReaderExec" for b in builds)
        # a coalesced reader reads the files of several buckets (four
        # producers wrote each of the fan-out's buckets, of either side)
        # and hands them on as ONE batch (ISSUE 45: the group's rows
        # placed once; a batch a FILE before), so a build is made of one
        # piece whatever its files
        first_join = [b for b in builds if b["stage"] == rule["stage"]]
        assert len(first_join) == rule["to"]
        reads = named(run, "shuffle.read")
        first_reads = [r for r in reads if r["stage"] == rule["stage"]]
        assert len(first_reads) == 2 * rule["to"]
        assert sum(r["pieces"] for r in first_reads) == 2 * 4 * fan_out
        second_reads = [r for r in reads if r["stage"] == rule["stage"] + 1]
        assert len(second_reads) == 2 * fan_out
        assert all(r["pieces"] == 4 for r in second_reads)
        assert all(b["pieces"] == 1 for b in builds)
        # the law of a group: ceil(rows / DEFAULT_BATCH_CAPACITY) batches,
        # their arrays (this query's shuffled columns carry no null: a
        # batch's columns, its selection and its row count) in one call
        assert all(r["batches"] == 1 and r["uploads"] >= 3 for r in reads)
        by_task = Counter(r["task"] for r in first_reads + second_reads)
        assert set(by_task.values()) == {2}
        # the second join's build is still ONE lax.sort a task, at the
        # capacity the parent sorted at: 16,384 slots for some 9,000
        # lineitem rows a partition (the parent laid its four files'
        # rungs end to end: 4 x 4,096 in 16 of 18 tasks and 14,336 in
        # two, a second sort shape the one-pass reader no longer makes)
        second_join = [b for b in builds if b["stage"] != rule["stage"]]
        assert len(second_join) == fan_out
        assert len({b["task"] for b in second_join}) == fan_out
        assert {b["capacity"] for b in second_join} == {16384}
        assert all(8000 < b["rows"] < 10000 for b in second_join)


@pytest.mark.parametrize("query", ["q3", "q14"])
def test_no_build_is_carried_from_one_query_to_the_next(served, query):
    _, runs, _ = served
    for run in runs[query]:
        assert named(run, "join.build")
        assert not named(run, "join.build_reused")
        assert run["added"].get("join.build_reused", 0) == 0


def test_every_scan_after_the_first_execution_is_served_from_the_device(
        served):
    _, runs, _ = served
    for query, scans in (("q3", 12), ("q14", 8)):
        for run in runs[query][1:]:
            served_how = Counter(e["how"] for e in named(run, "scan.serve"))
            # (a speculated duplicate of a scan task scans once more)
            assert set(served_how) == {"resident"}, (query, served_how)
            assert served_how["resident"] >= scans


def test_one_shuffle_write_a_shuffling_task_and_the_row_sums_them(served):
    _, runs, _ = served
    run = runs["q3"][-1]
    events = named(run, "shuffle.write")
    assert len({e["task"] for e in events}) == len(events)
    assert all(e["slices"] == e["batches"] * e["fan_out"] and e["batches"]
               for e in events)
    rows = [(st["num_tasks"], op["metrics"])
            for st in run["stages"].values() for op in st["operators"]
            if op["operator"] == "ShuffleWrite"]
    # scans of three tables and the first join shuffle; the second join's
    # and the aggregates' stages write one partition each
    assert len(rows) == 4 and sum(n for n, _ in rows) == len(events)
    for key, attr in (("shuffle_slices", "slices"),
                      ("shuffle_batches", "batches"),
                      ("shuffle_fan_out", "fan_out")):
        assert sum(m[key] for _, m in rows) == sum(e[attr] for e in events)
    assert sum(m["bytes_written"] for _, m in rows) == \
        sum(e["bytes"] for e in events)
    # the shuffle readers' side: every piece a file of this host
    reads = named(run, "shuffle.read")
    assert reads and all(r["local"] == r["pieces"] > 0 for r in reads)
    assert sum(r["pieces"] for r in reads if r["pieces"] > 1) >= \
        sum(e["fan_out"] for e in events)


def test_a_batch_costs_a_read_of_its_destinations_and_one_of_each_column(
        served):
    """reads = batches x (1 + columns), by task, whatever the fan-out: the
    one-pass write (ISSUE 44) reads a batch's destination vector and each
    of its columns once, and the event's ``reads`` is the spans' count."""
    _, runs, _ = served
    run = runs["q3"][-1]
    blocked = [r for r in run["events"] if r["name"] == "device.block"
               and r.get("site") == "ipc.batch_to_arrow"]
    assert len(blocked) == run["added"][READ]  # the ring held them all
    writes = named(run, "shuffle.write")
    assert writes
    for event in writes:
        mine = [r for r in blocked if r.get("task") == event["task"]]
        columns = len({r["col"] for r in mine if "col" in r})
        assert event["fan_out"] > 8 and event["batches"]
        assert len(mine) == event["reads"] == \
            event["batches"] * (1 + columns), (event, len(mine), columns)
        assert event["slices"] == event["batches"] * event["fan_out"]
    rows = [op["metrics"] for st in run["stages"].values()
            for op in st["operators"] if op["operator"] == "ShuffleWrite"]
    assert sum(m["shuffle_reads"] for m in rows) == \
        sum(e["reads"] for e in writes)
    # the shuffling tasks' reads are the events' sum (the stages that
    # write ONE partition, unshuffled, read a mask and each column too).
    # The first join hands its writer one batch a task since ISSUE 45,
    # where it handed one a producer FILE (12-20 here): its tasks' reads
    # fell with its batches, 80 -> 4 a task
    shuffling = {e["task"] for e in writes}
    theirs = sum(1 for r in blocked if r.get("task") in shuffling)
    assert theirs == sum(e["reads"] for e in writes) > 0
    (rule,) = named(run, "adaptive.rule")
    first_join = [e for e in writes if e["stage"] == rule["stage"]]
    assert first_join and all(e["batches"] == 1 for e in first_join)


def test_no_report_sat_out_a_wait(served):
    _, runs, _ = served
    for query in runs:
        for run in runs[query][1:]:
            assert run["added"].get("executor.report_waited", 0) == 0


def test_a_finished_jobs_files_go_once_the_client_has_its_result(served):
    _, runs, left = served
    assert all(not jobs for jobs in left.values()), left
    released = sum(run["added"].get("dataplane.release", 0)
                   for query in runs for run in runs[query])
    # the last job's release may land after its query's delta was taken
    assert released >= sum(EXECUTIONS.values()) - 1
