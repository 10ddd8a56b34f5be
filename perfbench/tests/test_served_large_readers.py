"""The readers ``served-join-large`` brings: the cell and its files are found
by name; the four over ``span_totals()`` on hand-made snapshots and on the
program itself; the two over the ``ShuffleWrite`` rows of the window's
recorded queries; and that a parent without ``shuffle.write`` and
``shuffle.read`` gives the three that read them nothing, while the three
over spans it has read as they do on the change."""

import pytest

import run

CELL = "served-join-large"
OLD = ("handoff_wait_s", "task_dispatch_s_per_query", "tasks_speculated",
       "result_fetch_s", "metrics_sync_share", "unnamed_idle_share")
NEW = ("shuffle_slices_per_query", "shuffle_fanout",
       "shuffle_d2h_reads_per_query", "shuffle_d2h_wait_s_per_query",
       "join_build_s_per_query", "shuffle_read_s_per_query")
READ = "device.block:ipc.batch_to_arrow"


def test_the_cell_and_its_files_are_found_by_name():
    cell = run.find_cell(CELL)
    config = cell["config"]
    assert cell["chips"] == 1 and config["mode"] == "served"
    assert (config["executors"], config["slots"], config["devices"]) == \
        (2, 2, 1)
    assert cell["traffic"]["round"] == ["q3", "q14"]
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [CELL]]
    assert listed == cell["reports"]["per_layer"] == list(OLD + NEW)
    assert all(m["moves"] in cell["reports"]["end_to_end"]
               for m in bench["per_layer"] if m["name"] in listed)
    for name in listed + cell["reports"]["end_to_end"]:
        assert hasattr(run.load_reader(name), "read")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpch-large-served")
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["scale"]
    # tpch-sf3-served but for the scale and what it forces to be said
    sf3 = run.read_json(run.HERE, "configs", "tpch-sf3-served.json")
    same = ("files_per_table", "format", "mode", "executors", "slots",
            "devices", "chips", "client_settings", "engine_defaults_kept",
            "warm_rounds_max", "environment")
    assert all(config[k] == sf3[k] for k in same)
    assert config["guarantees"][:2] == sf3["guarantees"]
    assert len(config["guarantees"]) == 4
    assert {"q14_limit_at_sf10", "cost_feedback"} < set(config["assumed"])


def obs_of(reader, before, after, queries=4):
    return {"snapshots": {reader: (before, after)},
            "window": {"queries": [{"query": "q3"}] * queries}}


def test_blocked_reads_of_the_shuffle_writes_a_query():
    count = run.load_reader("shuffle_d2h_reads_per_query")
    secs = run.load_reader("shuffle_d2h_wait_s_per_query")
    assert count.UNIT == "reads" and secs.UNIT == "s"
    before, after = {READ: (1000, 2.0)}, {READ: (24_000, 9.0)}
    assert count.read(obs_of("shuffle_d2h_reads_per_query", before,
                             after)) == pytest.approx(5750.0)
    assert secs.read(obs_of("shuffle_d2h_wait_s_per_query", before,
                            after)) == pytest.approx(1.75)
    # first seen in the window
    assert count.read(obs_of("shuffle_d2h_reads_per_query", {},
                             after)) == pytest.approx(6000.0)


def test_build_and_shuffle_read_seconds_a_query():
    build = run.load_reader("join_build_s_per_query")
    read = run.load_reader("shuffle_read_s_per_query")
    assert build.UNIT == "s" and read.UNIT == "s"
    assert build.read(obs_of(
        "join_build_s_per_query", {"join.build": (40, 10.0)},
        {"join.build": (88, 34.0)})) == pytest.approx(6.0)
    assert read.read(obs_of(
        "shuffle_read_s_per_query", {"shuffle.read": (10, 1.0)},
        {"shuffle.read": (210, 3.0)}, queries=8)) == pytest.approx(0.25)


def stage(tasks, fan_out, batches, written=1000, key="shuffle_"):
    """A stage of ``tasks`` shuffling tasks, each writing ``batches`` to
    ``fan_out`` destinations, as the scheduler sums their rows."""
    return {"num_tasks": tasks, "operators": [
        {"operator": "ShuffleReaderExec: 4 partitions", "depth": 1,
         "metrics": {"bytes_read": 10, "local_reads": 4}},
        {"operator": "ShuffleWrite", "depth": 0, "metrics": {
            "bytes_written": written, key + "fan_out": tasks * fan_out,
            key + "batches": tasks * batches,
            key + "slices": tasks * batches * fan_out}}]}


def last_stage():
    return {"num_tasks": 1, "operators": [
        {"operator": "PartitionWrite", "depth": 0,
         "metrics": {"bytes_written": 99}}]}


def recorded(*queries):
    return {"window": {"queries": [
        {"query": name, "started": 0.0, "seconds": 1.0,
         "record": {"phases": {}, "readers": {}, "stages": stages}}
        for name, stages in queries]}}


def test_slices_and_fan_out_come_from_the_shuffle_write_rows():
    slices = run.load_reader("shuffle_slices_per_query")
    fanout = run.load_reader("shuffle_fanout")
    assert slices.UNIT == "slices" and fanout.UNIT == "partitions"
    q3 = {1: stage(4, 17, 15), 2: stage(4, 17, 2), 3: stage(3, 17, 20),
          4: last_stage()}
    q14 = {1: stage(4, 8, 1), 2: last_stage()}
    obs = recorded(("q3", q3), ("q14", q14), ("q3", q3), ("q14", q14))
    a_q3 = 4 * 15 * 17 + 4 * 2 * 17 + 3 * 20 * 17
    assert slices.read(obs) == pytest.approx((a_q3 + 4 * 8) / 2)
    assert fanout.read(obs) == pytest.approx(
        (11 * 17 + 4 * 8) / (11 + 4))
    # a query that shuffled nothing is left out of both
    quiet = recorded(("q3", q3), ("q14", {1: last_stage()}))
    assert slices.read(quiet) == pytest.approx(a_q3)
    assert fanout.read(quiet) == pytest.approx(17.0)


def test_a_parent_without_the_events_prints_three_of_the_six():
    # its ShuffleWrite rows say bytes_written only, and its totals have no
    # shuffle.read key
    older = {1: {"num_tasks": 4, "operators": [
        {"operator": "ShuffleWrite", "depth": 0,
         "metrics": {"bytes_written": 1000}}]}, 2: last_stage()}
    obs = recorded(("q3", older), ("q14", older))
    obs["snapshots"] = {
        "shuffle_read_s_per_query": ({}, {}),
        "join_build_s_per_query": ({"join.build": (4, 1.0)},
                                   {"join.build": (44, 9.0)}),
        "shuffle_d2h_reads_per_query": ({READ: (0, 0.0)}, {READ: (800, 2.0)}),
        "shuffle_d2h_wait_s_per_query": ({READ: (0, 0.0)},
                                         {READ: (800, 2.0)})}
    got = {name: run.load_reader(name).read(obs) for name in NEW}
    assert [n for n in NEW if got[n] is None] == [
        "shuffle_slices_per_query", "shuffle_fanout",
        "shuffle_read_s_per_query"]
    assert got["join_build_s_per_query"] == pytest.approx(4.0)
    assert got["shuffle_d2h_reads_per_query"] == pytest.approx(400.0)
    assert got["shuffle_d2h_wait_s_per_query"] == pytest.approx(1.0)
    # no recorded query (a --trace 0 run), or a program without totals
    empty = {"window": {"queries": []},
             "snapshots": {n: (None, None) for n in NEW}}
    assert all(run.load_reader(n).read(empty) is None for n in NEW)


def test_the_snapshots_against_the_program_itself():
    from ballista_tpu.observability import tracing

    build = run.load_reader("join_build_s_per_query")
    read = run.load_reader("shuffle_read_s_per_query")
    reads = run.load_reader("shuffle_d2h_reads_per_query")
    with tracing.trace_span("join.build", side="t"):
        pass
    tracing.trace_event("join.build_reused", side="t")
    with tracing.trace_span("shuffle.read", pieces=1):
        pass
    with tracing.trace_span("device.block", site="ipc.batch_to_arrow"):
        pass
    with tracing.trace_span("device.block", site="join.stats"):
        pass
    # the name itself: ``join.build_reused`` is not a build made
    assert set(build.snapshot()) == {"join.build"}
    assert set(read.snapshot()) == {"shuffle.read"}
    assert set(reads.snapshot()) == {READ}
    assert all(count >= 1 for snap in (build.snapshot(), read.snapshot(),
                                       reads.snapshot())
               for count, _ in snap.values())
