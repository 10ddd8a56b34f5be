"""Shuffle-loss recovery: an executor dies after producing stage output;
the job must still complete.

The reference detects failures but never recovers (any failed task fails
the job, reference: rust/scheduler/src/state/mod.rs:342-346; leases at
:42,89 only age dead executors out of metadata). Here a tagged
ShuffleFetchError makes the scheduler reset + re-queue the lost producer
partitions, and lease-expired executors' running tasks are reaped.

Style: direct service calls + manually pumped executors (no poll-loop
timing), like the reference's tonic-without-network tests
(rust/scheduler/src/lib.rs:444-491)."""

import shutil
import time

import numpy as np
import pytest

from ballista_tpu import schema, col, sum_, Int64, Utf8, serde
from ballista_tpu.distributed.executor import Executor, ExecutorConfig
from ballista_tpu.distributed.scheduler import SchedulerService
from ballista_tpu.distributed.state import (
    EXECUTOR_LEASE_SECS,
    MemoryBackend,
    SchedulerState,
)
from ballista_tpu.distributed.types import PartitionId, TaskStatus
from ballista_tpu.errors import ShuffleFetchError
from ballista_tpu.logical import LogicalPlanBuilder
from ballista_tpu.proto import ballista_pb2 as pb


def _source(tmp_path):
    # two partition files -> a 2-task producer stage
    d = tmp_path / "t"
    d.mkdir()
    for part in range(2):
        lines = [f"{i}|k{i % 3}|" for i in range(60) if i % 2 == part]
        (d / f"part{part}.tbl").write_text("\n".join(lines) + "\n")
    from ballista_tpu.io import TblSource

    return TblSource(str(d), schema(("a", Int64), ("c", Utf8)))


def _submit_groupby(svc, src):
    plan = (
        LogicalPlanBuilder.scan("t", src)
        .aggregate([col("c")], [sum_(col("a")).alias("s")])
        .build()
    )
    params = pb.ExecuteQueryParams()
    params.logical_plan.CopyFrom(serde.plan_to_proto(plan))
    job_id = svc.ExecuteQuery(params).job_id
    deadline = time.time() + 10
    while not svc.state.stage_ids(job_id):
        assert time.time() < deadline, "planning never finished"
        time.sleep(0.05)
    # stage plans persist BEFORE the ready queue is seeded (enqueue_job
    # runs last in the planning thread); wait until tasks are actually
    # dispatchable or the first manual _pump races planning under load
    while not svc.state._ready:
        assert time.time() < deadline, "job never enqueued"
        time.sleep(0.05)
    return job_id


def _pump(svc, executor, run=True):
    """One manual poll cycle: report pending statuses, maybe run a task.
    Returns the PartitionId it ran (or None)."""
    params = pb.PollWorkParams(can_accept_task=run)
    params.metadata.id = executor.id
    params.metadata.host = executor.config.host
    params.metadata.port = executor.port
    params.metadata.num_devices = 1
    with executor._status_lock:
        for st in executor._pending_status:
            params.task_status.append(st)
        executor._pending_status.clear()
    result = svc.PollWork(params)
    if not (run and result.HasField("task")):
        return None
    td = result.task
    pid = PartitionId(td.task_id.job_id, td.task_id.stage_id,
                      td.task_id.partition_id)
    plan = serde.physical_from_proto(td.plan)
    shuffle = None
    if td.shuffle_output_partitions:
        hx = [serde.expr_from_proto(e) for e in td.shuffle_hash_exprs]
        shuffle = (hx or None, td.shuffle_output_partitions)
    try:
        stats = executor.execute_partition(pid, plan, shuffle)
        executor._report_completed(pid, stats)
    except Exception as e:  # noqa: BLE001 - report like the real loop
        executor._report_failed(pid, str(e))
    return pid


def _make_executor(tmp_path, name):
    return Executor(ExecutorConfig(
        work_dir=str(tmp_path / name), scheduler_port=1,
    ))


def test_job_survives_producer_executor_death(tmp_path):
    svc = SchedulerService(SchedulerState(MemoryBackend()))
    e1 = _make_executor(tmp_path, "e1")
    e2 = _make_executor(tmp_path, "e2")
    try:
        job_id = _submit_groupby(svc, _source(tmp_path))

        # e1 runs the whole producer (partial-aggregate) stage
        ran = [_pump(svc, e1), _pump(svc, e1)]
        assert all(r is not None for r in ran)
        _pump(svc, e1, run=False)  # report completions
        assert svc.state.get_job_status(job_id).state != "failed"

        # e1 dies: its shuffle files and data plane are gone
        e1._data_plane.close()
        shutil.rmtree(e1.config.work_dir)

        # e2 picks up the final stage, fails to fetch, reports the tagged
        # error; the scheduler re-queues the lost producer partitions
        pid = _pump(svc, e2)
        assert pid is not None and pid.stage_id != ran[0].stage_id
        _pump(svc, e2, run=False)
        st = svc.state.get_job_status(job_id)
        assert st.state != "failed", f"job failed instead of recovering: {st.error}"

        # e2 re-runs the producers and then the final stage to completion
        for _ in range(8):
            _pump(svc, e2)
            if svc.state.get_job_status(job_id).state == "completed":
                break
        status = svc.state.get_job_status(job_id)
        assert status.state == "completed", (status.state, status.error)

        # result correctness: read the final partition via the data plane
        from ballista_tpu.distributed.dataplane import fetch_partition_bytes
        from ballista_tpu.io import ipc

        locs = status.partition_locations
        got = {}
        for loc in locs:
            buf = fetch_partition_bytes("localhost", e2.port, loc.job_id,
                                        loc.stage_id, loc.partition_id)
            names, arrays, _, dicts, _ = ipc.read_partition_arrays(buf)
            # the registry hands back a resolved Dictionary (raw value
            # array only with BALLISTA_DICT_REGISTRY=off)
            dvals = np.asarray(getattr(dicts["c"], "values", dicts["c"]),
                               dtype=object)
            keys = dvals[arrays["c"]]
            for k, s in zip(keys, arrays["s"]):
                got[str(k)] = got.get(str(k), 0) + int(s)
        a = np.arange(60)
        exp = {f"k{r}": int(a[a % 3 == r].sum()) for r in range(3)}
        assert got == exp
    finally:
        for e in (e1, e2):
            try:
                e._data_plane.close()
            except Exception:  # noqa: BLE001 - already dead
                pass


def test_retry_budget_exhaustion_fails_job(tmp_path):
    svc = SchedulerService(SchedulerState(MemoryBackend()))
    state = svc.state
    job_id = "j000001"
    state.save_job_status(job_id, __import__(
        "ballista_tpu.distributed.types", fromlist=["JobStatus"]
    ).JobStatus("running"))
    # a fake 1-partition producer stage, already completed
    state.save_stage_plan(job_id, 1, b"", 1, [])
    state.save_task_status(TaskStatus(PartitionId(job_id, 1, 0), "completed",
                                      executor_id="gone"))
    state.save_stage_plan(job_id, 2, b"", 1, [1])
    consumer = TaskStatus(
        PartitionId(job_id, 2, 0), "failed",
        error=str(ShuffleFetchError(1, [0], "gone", "connection refused")),
    )
    for i in range(state.MAX_RECOVERIES_PER_JOB):
        assert state.recover_fetch_failure(consumer), f"recovery {i} refused"
        # producer "completes" again on a new executor each round
        state.save_task_status(TaskStatus(PartitionId(job_id, 1, 0),
                                          "completed", executor_id="e2"))
    # budget exhausted: recovery refuses, normal failure path applies
    assert not state.recover_fetch_failure(consumer)


def test_transient_task_failure_requeued():
    """IO-shaped task failures re-queue within budget; deterministic ones
    fail fast (the reference fails the job on any failure)."""
    from ballista_tpu.distributed.types import JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_job_status("j000003", JobStatus("running"))
    state.save_stage_plan("j000003", 1, b"", 1, [])
    pid = PartitionId("j000003", 1, 0)

    transient = TaskStatus(pid, "failed", error="IoError: disk hiccup")
    assert state.recover_transient_failure(transient)
    assert state.next_task() == pid
    assert state.get_task_statuses("j000003", 1)[0].state is None

    deterministic = TaskStatus(pid, "failed",
                               error="ExecutionError: capacity exceeded")
    assert not state.recover_transient_failure(deterministic)

    # budget: repeated transient failures eventually fail
    for _ in range(state.MAX_RECOVERIES_PER_JOB - 1):
        assert state.recover_transient_failure(transient)
    assert not state.recover_transient_failure(transient)


def test_shuffle_fetch_error_parse_with_class_prefix():
    e = ShuffleFetchError(3, [1, 2], "ex1", "connection refused")
    prefixed = f"{type(e).__name__}: {e}"
    assert ShuffleFetchError.parse(prefixed) == (3, [1, 2], "ex1")
    assert ShuffleFetchError.parse("ExecutionError: nope") is None


def test_speculative_execution_of_stragglers(tmp_path):
    """An idle executor gets a DUPLICATE of a long-running task (the
    reference has no speculation at all); first completion wins."""
    from ballista_tpu.observability.tracing import span_totals

    def speculated():
        return span_totals().get("scheduler.speculate",
                                 {"count": 0})["count"]

    svc = SchedulerService(SchedulerState(MemoryBackend()),
                           speculation_age_secs=0.05)
    e1 = _make_executor(tmp_path, "e1")
    e2 = _make_executor(tmp_path, "e2")
    speculated0 = speculated()
    try:
        job_id = _submit_groupby(svc, _source(tmp_path))
        # e1 takes both producer tasks but "hangs" (never reports back):
        # poll directly so the tasks are assigned without executing
        for _ in range(2):
            params = pb.PollWorkParams(can_accept_task=True)
            params.metadata.id = e1.id
            params.metadata.host = "localhost"
            params.metadata.port = e1.port
            params.metadata.num_devices = 1
            assert svc.PollWork(params).HasField("task")
        time.sleep(0.1)  # exceed the straggler threshold

        # e2 polls: ready queue is empty, so it receives DUPLICATES of
        # e1's stuck tasks and actually runs them
        ran = [_pump(svc, e2), _pump(svc, e2)]
        assert all(r is not None for r in ran)
        # each duplicate is one scheduler.speculate event, counted by
        # name and exported beside the dispatch counter
        assert speculated() - speculated0 == 2
        samples = {name: v for name, _, v in svc._metric_samples()}
        assert samples["ballista_tasks_speculated_total"] == speculated()
        for _ in range(6):
            _pump(svc, e2)
            if svc.state.get_job_status(job_id).state == "completed":
                break
        assert svc.state.get_job_status(job_id).state == "completed"
        # each task is duplicated at most once
        assert svc.state.speculative_task(age_secs=0.0) is None
        assert speculated() - speculated0 == 2
    finally:
        for e in (e1, e2):
            e._data_plane.close()


def test_reap_requeues_running_tasks_of_dead_executor(tmp_path):
    from ballista_tpu.distributed.types import ExecutorMeta, JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_executor_metadata(ExecutorMeta("live", "localhost", 1, 1))
    state.save_job_status("j000002", JobStatus("running"))
    state.save_stage_plan("j000002", 1, b"", 2, [])
    state.save_task_status(TaskStatus(PartitionId("j000002", 1, 0),
                                      "running", executor_id="dead"))
    state.save_task_status(TaskStatus(PartitionId("j000002", 1, 1),
                                      "running", executor_id="live"))
    state.reap_lost_tasks(min_interval_secs=0.0)
    # the dead executor's task is pending + queued again; the live one isn't
    statuses = {t.partition.partition_id: t.state
                for t in state.get_task_statuses("j000002", 1)}
    assert statuses == {0: None, 1: "running"}
    nxt = state.next_task()
    assert nxt == PartitionId("j000002", 1, 0)
    assert state.next_task() is None


def test_speculation_never_duplicates_onto_same_executor():
    """The executor already running a straggler must not receive its own
    duplicate: both copies would write the same deterministic work_dir
    path concurrently (single-executor clusters made this deterministic
    data corruption before the exclusion)."""
    from ballista_tpu.distributed.types import JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_job_status("j000003", JobStatus("running"))
    state.save_stage_plan("j000003", 1, b"", 1, [])
    state.save_task_status(TaskStatus(
        PartitionId("j000003", 1, 0), "running", executor_id="e1",
        started_at=time.time() - 120,
    ))
    # e1 (the straggler's own executor) asks: no duplicate
    assert state.speculative_task(age_secs=60.0, executor_id="e1",
                                  min_interval_secs=0.0) is None
    # a different executor gets the duplicate
    assert state.speculative_task(age_secs=60.0, executor_id="e2",
                                  min_interval_secs=0.0) == \
        PartitionId("j000003", 1, 0)


def test_first_completion_wins_on_duplicate_reports():
    """A speculative duplicate and the original can both finish; the
    SECOND completion report must be dropped so consumers keep fetching
    from the recorded (first) location."""
    from ballista_tpu.distributed.types import ExecutorMeta, JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_executor_metadata(ExecutorMeta("e1", "h1", 1, 1))
    state.save_executor_metadata(ExecutorMeta("e2", "h2", 2, 1))
    state.save_job_status("j000004", JobStatus("running"))
    state.save_stage_plan("j000004", 1, b"", 1, [])
    pid = PartitionId("j000004", 1, 0)
    state.task_completed(TaskStatus(pid, "completed", executor_id="e1",
                                    path="/w1/data.arrow"))
    state.task_completed(TaskStatus(pid, "completed", executor_id="e2",
                                    path="/w2/data.arrow"))
    (st,) = state.get_task_statuses("j000004", 1)
    assert st.executor_id == "e1" and st.path == "/w1/data.arrow"
    locs = state.stage_locations("j000004")[1]
    assert [(loc.host, loc.path) for loc in locs] == [("h1", "/w1/data.arrow")]


def test_unroutable_location_fails_resolution_with_tagged_error():
    """A completed task whose executor has NO address record (no lease,
    no durable record) must raise the tagged ShuffleFetchError at
    resolution time — never emit host='', port=0 for a consumer to trip
    over."""
    from ballista_tpu.distributed.types import JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_job_status("j000005", JobStatus("running"))
    state.save_stage_plan("j000005", 1, b"", 1, [])
    state.save_task_status(TaskStatus(
        PartitionId("j000005", 1, 0), "completed", executor_id="gone",
        path="/lost/data.arrow",
    ))
    with pytest.raises(ShuffleFetchError) as ei:
        state.stage_locations("j000005")
    assert ei.value.stage_id == 1 and ei.value.partition_ids == [0]


def test_atomic_partition_write_leaves_no_tmp(tmp_path):
    """write_partition goes through tmp+rename so a concurrent duplicate
    writer can never expose a half-written file."""
    from ballista_tpu.columnar import ColumnBatch
    from ballista_tpu.datatypes import Int64
    from ballista_tpu.io import ipc

    batch = ColumnBatch.from_numpy(
        schema(("a", Int64)), {"a": np.arange(8, dtype=np.int64)}
    )
    path = str(tmp_path / "j" / "1" / "0" / "data.arrow")
    stats = ipc.write_partition(path, [batch])
    assert stats["num_rows"] == 8
    leftovers = [p for p in (tmp_path / "j" / "1" / "0").iterdir()
                 if p.name != "data.arrow"]
    assert leftovers == []
    # overwrite (duplicate completing later) also lands atomically
    ipc.write_partition(path, [batch])
    names, arrays, _, _, _ = ipc.read_partition_arrays(path)
    assert names == ["a"] and len(arrays["a"]) == 8


def test_failure_report_cannot_clobber_completed_task():
    """The losing speculative duplicate may FAIL after the original
    completed; that failure report must be dropped (no status clobber,
    no spurious recovery)."""
    from ballista_tpu.distributed.types import ExecutorMeta, JobStatus

    svc = SchedulerService(SchedulerState(MemoryBackend()))
    state = svc.state
    state.save_executor_metadata(ExecutorMeta("e1", "h1", 1, 1))
    state.save_job_status("j000006", JobStatus("running"))
    state.save_stage_plan("j000006", 1, b"", 1, [])
    pid = PartitionId("j000006", 1, 0)
    state.task_completed(TaskStatus(pid, "completed", executor_id="e1",
                                    path="/w1/data.arrow"))
    params = pb.PollWorkParams(can_accept_task=False)
    params.metadata.id = "e2"
    params.metadata.host = "h2"
    params.metadata.port = 2
    params.metadata.num_devices = 1
    ts = params.task_status.add()
    ts.partition_id.job_id = "j000006"
    ts.partition_id.stage_id = 1
    ts.partition_id.partition_id = 0
    ts.failed.error = "IoError: disk full on the duplicate"
    svc.PollWork(params)
    (st,) = state.get_task_statuses("j000006", 1)
    assert st.state == "completed" and st.path == "/w1/data.arrow"


def test_first_failure_of_speculated_task_is_absorbed():
    """When a task has an in-flight speculative duplicate, ONE failure
    report must not fail the job (the twin may still succeed); a second
    failure flows through the normal path."""
    from ballista_tpu.distributed.types import JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_job_status("j000007", JobStatus("running"))
    state.save_stage_plan("j000007", 1, b"", 1, [])
    pid = PartitionId("j000007", 1, 0)
    state.save_task_status(TaskStatus(pid, "running", executor_id="e1",
                                      started_at=time.time() - 120))
    dup = state.speculative_task(age_secs=60.0, executor_id="e2",
                                 min_interval_secs=0.0)
    assert dup == pid
    assert state.absorb_speculative_failure(pid)      # first: absorbed
    assert not state.absorb_speculative_failure(pid)  # second: real
    # a task WITHOUT a duplicate never absorbs
    other = PartitionId("j000007", 1, 99)
    assert not state.absorb_speculative_failure(other)
