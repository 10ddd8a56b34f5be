"""The served hand-off's waits end on the event they wait for (PR 26).

On a CPU ``LocalCluster``, asserting on events and ledger phases: a
finished task's report leaves at once (``executor.report_now``,
``report_wait``); every free slot is filled before the first report
arrives; an idle cluster picks a job up from a held ``PollWork``
(``dispatch_wait``); a report never travels behind its own executor's
held call; past the cap on held calls the scheduler answers at once and
the job still completes; a failed poll re-delivers its reports and
keeps its slot; one-shot status reads are never held.
"""

import os
import threading
import time

import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.datatypes import Int64, Utf8, schema
from ballista_tpu.distributed import executor as executor_mod
from ballista_tpu.distributed.client import fetch_job_progress
from ballista_tpu.distributed.executor import (Executor, ExecutorConfig,
                                               LocalCluster,
                                               POLL_INTERVAL_SECS)
from ballista_tpu.distributed.scheduler import serve_scheduler
from ballista_tpu.distributed.state import MemoryBackend, SchedulerState
from ballista_tpu.distributed.types import JobStatus, PartitionId
from ballista_tpu.observability import tracing as obs_tracing
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.testing.faults import reload_faults

SQL = "SELECT k, sum(a) AS s FROM t GROUP BY k ORDER BY k"
EXPECTED = [39600, 40000]  # a part: x 9900, y 10000; four parts


def _count(name):
    return obs_tracing.span_totals().get(name, {"count": 0})["count"]


@pytest.fixture
def table_dir(tmp_path):
    """Four part files: the first stage has four tasks, the query
    three stages (4 + 1 + 1 tasks)."""
    d = tmp_path / "t"
    d.mkdir()
    for part in range(4):
        with open(d / f"part-{part}.csv", "w") as f:
            f.write("k,a\n")
            for i in range(200):
                f.write(f"{'xy'[i % 2]},{i}\n")
    return str(d)


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.delenv("BALLISTA_FLIGHT_RECORDER", raising=False)
    monkeypatch.setenv("BALLISTA_FLIGHT_RECORDER_SPANS", "16384")
    obs_tracing.reconfigure()
    ring = obs_tracing._ring()
    ring.clear()
    yield ring
    obs_tracing.reconfigure()


@pytest.fixture
def slow_tasks():
    """Every task sleeps 1.5 s before it runs: a window in which no
    report can arrive."""
    saved = os.environ.get("BALLISTA_FAULTS")
    os.environ["BALLISTA_FAULTS"] = "executor.task.start=delay:1500"
    reload_faults()
    yield
    if saved is None:
        os.environ.pop("BALLISTA_FAULTS", None)
    else:
        os.environ["BALLISTA_FAULTS"] = saved
    reload_faults()


def _context(cluster, table_dir):
    ctx = BallistaContext.remote("localhost", cluster.port)
    ctx.register_csv("t", table_dir, schema(("k", Utf8), ("a", Int64)))
    return ctx


def _warm_ledgers(ctx, runs=3):
    """Ledgers of ``runs`` warm executions, each started on a cluster
    that has been idle for more than an interval."""
    assert list(ctx.sql(SQL).collect()["s"]) == EXPECTED  # compiles
    out = []
    for _ in range(runs):
        time.sleep(1.5 * POLL_INTERVAL_SECS)
        assert list(ctx.sql(SQL).collect()["s"]) == EXPECTED
        out.append(ctx.last_query_ledger())
    return out


def test_reports_leave_on_completion_and_idle_executors_are_woken(
        table_dir):
    """Proved by the events themselves, not by the wall clock (a loaded
    test machine stretches every phase): which call carried a report,
    which wait an event ended."""
    before = {n: _count(n) for n in (
        "executor.report_now", "executor.report_waited", "executor.refill",
        "scheduler.poll_woken", "scheduler.status_woken",
        "scheduler.hold_refused")}
    cluster = LocalCluster(num_executors=2)
    try:
        ledgers = _warm_ledgers(_context(cluster, table_dir))
    finally:
        cluster.shutdown()
    rose = {n: _count(n) - before[n] for n in before}
    # 4 queries of 3 stages: each stage's end sent a poll of its own
    # (tasks of one executor that end together share one), and no
    # report sat out a timer's wait
    assert rose["executor.report_now"] >= 12, rose
    assert rose["executor.report_waited"] == 0, rose
    assert rose["executor.refill"] > 0, rose
    # submitted to an idle cluster: one held poll at least ended with a
    # task for each idle start, no hold was refused, and every warm
    # job's terminal status woke its waiting client
    assert rose["scheduler.poll_woken"] >= 3, rose
    assert rose["scheduler.hold_refused"] == 0, rose
    assert rose["scheduler.status_woken"] >= 3, rose
    # the ledger names the three waits; how long they took is the
    # benchmark's to say (sched_wait_s), on a machine of its own
    for led in ledgers:
        assert {"report_wait", "dispatch_wait",
                "client_poll_wait"} <= set(led["phases"]), led


def test_every_free_slot_is_filled_before_the_first_report(table_dir,
                                                           slow_tasks):
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    done = []
    try:
        ctx = _context(cluster, table_dir)
        th = threading.Thread(
            target=lambda: done.append(list(ctx.sql(SQL).collect()["s"])))
        th.start()
        deadline = time.time() + 1.2  # tasks sleep 1.5 s before running
        running = []
        while time.time() < deadline and len(running) < 4:
            time.sleep(0.02)
            running = [t for j in cluster.state._handoff
                       for t in cluster.state.get_task_statuses(j, 1)
                       if t.state == "running"]
        assert len(running) == 4, running
        # handed out two to an executor, and nothing has been reported
        per_executor = sorted(
            sum(1 for t in running if t.executor_id == e.id)
            for e in cluster.executors)
        assert per_executor == [2, 2]
        assert all(len(h["out"]) == 4
                   for h in cluster.state._handoff.values())
        th.join(timeout=60)
        assert not th.is_alive()
        assert done == [EXPECTED]
    finally:
        cluster.shutdown()


def test_a_report_is_not_stuck_behind_its_executors_held_poll(
        table_dir, ring, monkeypatch):
    # one executor, two slots, one task at a time in the later stages:
    # while that task runs the poll thread offers the other slot in a
    # call the scheduler holds, here for a whole second
    monkeypatch.setattr(executor_mod, "POLL_INTERVAL_SECS", 1.0)
    cluster = LocalCluster(num_executors=1, concurrent_tasks=2)
    try:
        ledgers = _warm_ledgers(_context(cluster, table_dir), runs=2)
    finally:
        cluster.shutdown()
    held = [r for r in ring if r["name"] == "scheduler.poll_held"]
    reports = [r for r in ring if r["name"] == "executor.poll"
               and r.get("reports")]
    assert held and reports
    # some report was sent while the executor's own call was held
    overtook = [r for r in reports for h in held
                if h["ts"] < r["ts"] < h["ts"] + h["dur"]]
    assert overtook
    # and no stage waited out the hold: each report_wait is a fraction
    # of the second a held call lasts
    assert min(led["phases"]["report_wait"] for led in ledgers) < 0.1


def test_past_the_cap_calls_are_answered_at_once_and_jobs_complete(
        table_dir):
    refused0 = _count("scheduler.hold_refused")
    held0 = _count("scheduler.poll_held")
    cluster = LocalCluster(num_executors=3)
    try:
        assert cluster.service.max_held_calls == 8  # half of 16 workers
        cluster.service.max_held_calls = 1
        ctx = _context(cluster, table_dir)
        for _ in range(2):
            assert list(ctx.sql(SQL).collect()["s"]) == EXPECTED
        time.sleep(3 * POLL_INTERVAL_SECS)
        assert cluster.service._held_calls <= 1
    finally:
        cluster.shutdown()
    assert _count("scheduler.hold_refused") > refused0
    assert _count("scheduler.poll_held") > held0
    assert cluster.service._held_calls == 0


def test_status_holds_end_on_the_terminal_status_or_are_refused():
    state = SchedulerState(MemoryBackend())
    server, svc, port = serve_scheduler(state, "localhost", 0,
                                        max_workers=4)
    try:
        assert svc.max_held_calls == 2
        state.save_job_status("j1", JobStatus("queued"))
        ask = pb.GetJobStatusParams(job_id="j1", wait_secs=0.8)
        # a one-shot read is never held
        held0 = _count("scheduler.status_held")
        t0 = time.monotonic()
        fetch_job_progress("localhost", port, "j1")
        assert time.monotonic() - t0 < 0.4
        assert _count("scheduler.status_held") == held0
        # no worker to spare: answered at once, and counted
        svc.max_held_calls = 0
        refused0 = _count("scheduler.hold_refused")
        t0 = time.monotonic()
        assert svc.GetJobStatus(ask).status.WhichOneof("status") == "queued"
        assert time.monotonic() - t0 < 0.4
        assert _count("scheduler.hold_refused") == refused0 + 1
        # held, and ended by the bound
        svc.max_held_calls = 2
        woken0 = _count("scheduler.status_woken")
        t0 = time.monotonic()
        assert svc.GetJobStatus(ask).status.WhichOneof("status") == "queued"
        assert time.monotonic() - t0 >= 0.8
        assert _count("scheduler.status_woken") == woken0
        # held, and ended by the event
        got = []
        th = threading.Thread(target=lambda: got.append(
            svc.GetJobStatus(pb.GetJobStatusParams(job_id="j1",
                                                   wait_secs=30.0))))
        th.start()
        time.sleep(0.1)
        t0 = time.monotonic()
        state.save_job_status("j1", JobStatus("failed", error="boom"))
        th.join(timeout=5)  # MAX_HOLD_SECS bounds a hold at 1 s anyway
        assert not th.is_alive()
        assert time.monotonic() - t0 < 0.5
        assert got[0].status.failed.error == "boom"
        assert _count("scheduler.status_woken") == woken0 + 1
        assert svc._held_calls == 0
    finally:
        server.stop(grace=None)
        svc.close_health()


def test_a_failed_poll_redelivers_its_reports_and_keeps_its_slot(tmp_path):
    state = SchedulerState(MemoryBackend())
    server, svc, port = serve_scheduler(state, "localhost", 0)
    ex = None
    try:
        state.save_job_status("j1", JobStatus("running"))
        state.save_stage_plan("j1", 1, b"", 1, [])
        ex = Executor(ExecutorConfig(work_dir=str(tmp_path / "w"),
                                     scheduler_port=port,
                                     concurrent_tasks=1))
        ex._report_completed(
            PartitionId("j1", 1, 0),
            {"path": "/w/data.arrow", "num_rows": 3, "num_bytes": 64})
        real = ex._client

        class Down:
            def PollWork(self, params):
                raise ConnectionError("scheduler down")

        ex._client = Down()
        for hold in (True, False):  # the timer's poll and the report's
            with pytest.raises(ConnectionError):
                ex._poll_once(hold=hold)
            assert len(ex._pending_status) == 1
            assert ex._has_free_slot() and ex._asking == 0
        assert state.get_task_statuses("j1", 1) == []
        ex._client = real
        assert ex._poll_once() is False  # no task came
        (st,) = state.get_task_statuses("j1", 1)
        assert st.state == "completed" and st.path == "/w/data.arrow"
        assert ex._pending_status == [] and ex._has_free_slot()
    finally:
        if ex is not None:
            ex.stop()
        server.stop(grace=None)
        svc.close_health()


def test_offers_and_holds_stay_inside_their_bounds_under_contention(
        tmp_path):
    """More threads than cores on the two counters the protocol shares
    between threads: the slots an executor's polls offer (never more in
    flight than it has) and the calls the scheduler holds (never more
    than the cap); both return to zero."""
    import sys

    ex = Executor(ExecutorConfig(work_dir=str(tmp_path / "w"),
                                 scheduler_port=1, concurrent_tasks=2))
    svc_state = SchedulerState(MemoryBackend())
    server, svc, _port = serve_scheduler(svc_state, "localhost", 0,
                                         max_workers=6)
    lock = threading.Lock()
    seen = {"offers": 0, "most_offers": 0, "most_held": 0}

    class Scheduler:
        def PollWork(self, params):
            if params.can_accept_task:
                with lock:
                    seen["offers"] += 1
                    seen["most_offers"] = max(seen["most_offers"],
                                              seen["offers"])
                time.sleep(0.0002)
                with lock:
                    seen["offers"] -= 1
            return pb.PollWorkResult()

    ex._client = Scheduler()

    def work(i):
        for n in range(150):
            ex._poll_once(hold=bool((i + n) % 2))
            if svc._begin_hold():
                with lock:
                    seen["most_held"] = max(seen["most_held"],
                                            svc._held_calls)
                svc._end_hold()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        ex.stop()
        server.stop(grace=None)
        svc.close_health()
    assert not any(t.is_alive() for t in threads)
    assert 1 <= seen["most_offers"] <= 2, seen
    assert 1 <= seen["most_held"] <= svc.max_held_calls == 3, seen
    assert ex._asking == 0 and svc._held_calls == 0
    assert ex._slots.acquire(blocking=False) and \
        ex._slots.acquire(blocking=False)      # both slots came back
    assert not ex._slots.acquire(blocking=False)
