"""The served hand-off in the program's own spans (PR 25).

Covers: the three hand-off phases of the latency ledger on a LocalCluster
(``dispatch_wait``, ``report_wait``, ``client_poll_wait``: wall time,
disjoint, the ledger still reconstructs); the scheduler state's
accounting of the first two and the log's patch for the third; spans,
events and ``ledger_phase`` stamps as host events of a ``jax.profiler``
trace, read back through ``perfbench/xplane.load``; ``span_totals()``
exact beyond the flight recorder's ring and across threads; poll waits
of an idle cluster kept out of the ring. Since PR 26 the waits end on
the event they wait for (tests/test_handoff_events.py); what is pinned
here is that the phases are still there and still wall time.
"""

import os
import sys
import threading
import time

import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.datatypes import Int64, Utf8, schema
from ballista_tpu.distributed.state import MemoryBackend, SchedulerState
from ballista_tpu.distributed.types import PartitionId, TaskStatus
from ballista_tpu.observability import ledger as obs_ledger
from ballista_tpu.observability import tracing as obs_tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
POLL_SPANS = ("executor.poll_wait", "executor.poll",
              "client.poll_wait", "client.poll",
              "scheduler.poll_held", "scheduler.hold_refused")


@pytest.fixture
def fresh_ring(monkeypatch):
    """A flight recorder of the default size, empty, whatever ran
    before in this worker."""
    monkeypatch.delenv("BALLISTA_FLIGHT_RECORDER", raising=False)
    monkeypatch.setenv("BALLISTA_FLIGHT_RECORDER_SPANS", "4096")
    obs_tracing.reconfigure()
    ring = obs_tracing._ring()
    ring.clear()
    yield ring
    obs_tracing.reconfigure()


# -- (a) the ledger's hand-off phases, end to end ---------------------------


def test_cluster_ledger_has_the_handoff_phases(tmp_path):
    from ballista_tpu.distributed.executor import LocalCluster

    csv = tmp_path / "t.csv"
    with open(csv, "w") as f:
        f.write("k,a\n")
        for i in range(40):
            f.write(f"{'xy'[i % 2]},{i}\n")
    obs_ledger.reset_process_log()
    cluster = LocalCluster(num_executors=2)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port)
        ctx.register_csv("t", str(csv), schema(("k", Utf8), ("a", Int64)))
        sql = "SELECT k, sum(a) AS s FROM t GROUP BY k ORDER BY k"
        ctx.sql(sql).collect()            # compiles
        out = ctx.sql(sql).collect()      # the two-stage query, warm
        assert list(out["s"]) == [380, 400]
        led = ctx.last_query_ledger()
        assert set(led["phases"]) == set(obs_ledger.LEDGER_PHASES)
        handoff = {p: led["phases"][p] for p in obs_ledger.HANDOFF_PHASES}
        assert all(v >= 0.0 for v in handoff.values()), handoff
        # ready tasks go to polls the scheduler is holding: picked up
        # inside the interval they used to wait out, and still timed
        from ballista_tpu.distributed.executor import POLL_INTERVAL_SECS

        assert 0.0 < handoff["dispatch_wait"] < POLL_INTERVAL_SECS, handoff
        assert sum(handoff.values()) <= led["wall_seconds"] + 1e-6
        # the scheduler's own row: phases + remainder are the wall time,
        # client_poll_wait included (it lies after the terminal
        # transition, so the patch grew the wall by as much)
        row = [e for e in obs_ledger.process_ledger_log().entries()
               if e["job_id"] == ctx._last_job_id][-1]
        assert row["phases"]["client_poll_wait"] > 0.0
        total = sum(row["phases"].values())
        if total <= row["wall_seconds"]:
            assert total + row["unattributed_seconds"] == pytest.approx(
                row["wall_seconds"], abs=1e-4)
        else:  # task thread-seconds side by side can pass the wall
            assert row["unattributed_seconds"] == 0.0
        for led_ in (led, row):
            total = sum(led_["phases"].values())
            assert total + led_["unattributed_seconds"] >= \
                led_["wall_seconds"] - 1e-4
        # a second status read must not count client_poll_wait again
        before = row["phases"]["client_poll_wait"]
        from ballista_tpu.distributed.client import fetch_job_progress

        fetch_job_progress("localhost", cluster.port, ctx._last_job_id)
        again = [e for e in obs_ledger.process_ledger_log().entries()
                 if e["job_id"] == ctx._last_job_id][-1]
        assert again["phases"]["client_poll_wait"] == before
    finally:
        cluster.shutdown()


# -- the accounting behind (a), on the state alone --------------------------


def _two_stage_state():
    state = SchedulerState(MemoryBackend())
    state.save_stage_plan("j1", 1, b"", 2, [])
    state.save_stage_plan("j1", 2, b"", 1, [1])
    for sid, n in ((1, 2), (2, 1)):
        for p in range(n):
            state.save_task_status(TaskStatus(PartitionId("j1", sid, p)))
    return state


def _complete(state, pid, report_wait=0.0):
    state.task_reported(pid)
    state.task_completed(TaskStatus(pid, "completed", executor_id="e1"),
                         report_wait=report_wait)


def test_dispatch_wait_runs_only_while_ready_and_none_out():
    state = _two_stage_state()
    state.enqueue_job("j1")               # two tasks ready, none out
    time.sleep(0.05)
    a = state.next_task()                 # one out: the clock stops
    waited = state._handoff["j1"]["dispatch_wait"]
    assert waited >= 0.05
    time.sleep(0.05)                      # b is ready, but a is out
    b = state.next_task()
    assert state._handoff["j1"]["dispatch_wait"] == waited
    _complete(state, a)
    time.sleep(0.03)                      # b out, nothing ready
    _complete(state, b)                   # stage 1 done: stage 2 ready
    assert state._handoff["j1"]["since"] is not None
    time.sleep(0.05)
    c = state.next_task()
    assert c == PartitionId("j1", 2, 0)
    took = state.take_handoff("j1")
    assert waited + 0.05 <= took["dispatch_wait"] < waited + 0.05 + 0.03
    assert state.take_handoff("j1") == {}  # once a job


def test_report_wait_counts_the_stage_completing_report_as_wall_time():
    state = _two_stage_state()
    state.enqueue_job("j1")
    a, b = state.next_task(), state.next_task()
    _complete(state, a, report_wait=0.2)   # not the last of its stage
    assert state._handoff["j1"]["report_wait"] == 0.0
    _complete(state, b, report_wait=0.1)   # completes stage 1
    assert state._handoff["j1"]["report_wait"] == pytest.approx(0.1,
                                                                abs=0.01)
    c = state.next_task()
    # stage 2's report claims 10 s of waiting, 0 s after stage 1's
    # arrived: only the part no earlier report covers is wall time
    _complete(state, c, report_wait=10.0)
    took = state.take_handoff("j1")
    assert took["report_wait"] == pytest.approx(0.1, abs=0.02)


def test_client_poll_wait_is_taken_once_and_patches_the_row():
    from ballista_tpu.distributed.types import JobStatus

    state = SchedulerState(MemoryBackend())
    state.save_job_status("j9", JobStatus("queued"))
    assert state.take_terminal_at("j9") is None
    state.save_job_status("j9", JobStatus("completed"))
    stamped = state.take_terminal_at("j9")
    assert stamped is not None and stamped <= time.time()
    assert state.take_terminal_at("j9") is None
    log = obs_ledger.LedgerLog(capacity=4)
    log.record(obs_ledger.build_ledger(
        "j9", 1.0, "cluster", "completed", {"planning": 0.25}))
    assert log.add_phase("j9", "client_poll_wait", 0.06)
    row = log.last()
    assert row["phases"]["client_poll_wait"] == pytest.approx(0.06)
    assert row["wall_seconds"] == pytest.approx(1.06)
    assert sum(row["phases"].values()) + row["unattributed_seconds"] == \
        pytest.approx(row["wall_seconds"])
    assert not log.add_phase("nope", "client_poll_wait", 1.0)


def test_task_sums_leave_the_handoff_phases_out():
    payloads = [{"phases": {"ledger.report_wait": 0.2,
                            "ledger.shuffle_write": 0.5}},
                {"phases": {"ledger.report_wait": 0.3,
                            "ledger.shuffle_write": 0.25}}]
    assert obs_ledger.merge_task_phases(payloads) == {"shuffle_write": 0.75}
    # a stamped client span in a mined window is not summed a second time
    window = [{"name": "client.planning", "dur": 0.4},
              {"name": "shuffle.fetch", "dur": 0.1}]
    assert obs_ledger.span_phase_sums(window) == {"shuffle_fetch": 0.1}


# -- (b) one clock with the device trace ------------------------------------


@pytest.mark.parametrize("kind,name", [
    ("span", "executor.poll_wait"),
    ("ledger_phase", "client.result_transfer"),
    ("event", "scheduler.speculate"),
])
def test_program_spans_are_host_events_of_a_profiler_trace(tmp_path, kind,
                                                          name):
    import jax

    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    try:
        import xplane
    finally:
        sys.path.pop(0)
    assert not jax.profiler.TraceAnnotation.is_enabled()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=xplane.profiler_options())
    try:
        if kind == "span":
            with obs_tracing.trace_span(name, executor="e1"):
                time.sleep(0.01)
        elif kind == "ledger_phase":
            obs_ledger.begin_collect()
            with obs_ledger.ledger_phase("result_transfer"):
                time.sleep(0.01)
            stamps = obs_ledger.take_collect()
        else:
            obs_tracing.trace_event(name, task="j/1/0")
    finally:
        jax.profiler.stop_trace()
    host = [ev for p in xplane.load(str(tmp_path))
            if not p["name"].startswith("/device:")
            for ln in p["lines"] for ev in ln["events"]]
    found = [ev for ev in host if ev[0] == name]
    assert len(found) == 1, sorted({ev[0] for ev in host})[:40]
    if kind != "event":
        assert found[0][2] >= 10_000_000  # nanoseconds: the 10 ms slept
    if kind == "ledger_phase":
        # the stamp and the span are one clock reading
        assert stamps["result_transfer"] == pytest.approx(
            found[0][2] / 1e9, abs=5e-3)
        assert stamps["result_transfer"] >= 0.01


# -- (c) totals no ring bounds ----------------------------------------------


def test_span_totals_count_beyond_the_ring_from_four_threads(fresh_ring):
    before = obs_tracing.span_totals().get(
        "device.block", {"count": 0, "seconds": 0.0})

    def work():
        for _ in range(1250):
            with obs_tracing.trace_span("device.block", site="test"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # a lost update would show
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    after = obs_tracing.span_totals()["device.block"]
    assert after["count"] - before["count"] == 5000
    assert after["seconds"] >= before["seconds"]
    assert len(fresh_ring) == 4096  # the ring kept the newest only
    # with the recorder off the totals still count
    os.environ["BALLISTA_FLIGHT_RECORDER"] = "0"
    obs_tracing.reconfigure()
    try:
        with obs_tracing.trace_span("device.block", site="test") as span:
            time.sleep(0.002)
        obs_tracing.trace_event("scheduler.speculate")
        assert not obs_tracing.flight_recorder_enabled()
        assert span.dur >= 0.002
        got = obs_tracing.span_totals()
        assert got["device.block"]["count"] - before["count"] == 5001
        assert got["scheduler.speculate"]["count"] >= 1
    finally:
        del os.environ["BALLISTA_FLIGHT_RECORDER"]


def test_a_span_can_stay_out_of_the_ring_and_still_count(fresh_ring):
    n0 = obs_tracing.span_totals().get("executor.poll_wait",
                                       {"count": 0})["count"]
    with obs_tracing.trace_span("executor.poll_wait") as span:
        span.record = False
    with obs_tracing.trace_span("executor.poll_wait", executor="e1"):
        pass
    kept = [r for r in fresh_ring if r["name"] == "executor.poll_wait"]
    assert len(kept) == 1 and kept[0]["executor"] == "e1"
    assert obs_tracing.span_totals()["executor.poll_wait"]["count"] == n0 + 2
    # the span stack is left as it was found
    with obs_tracing.trace_span("executor.task") as outer:
        with obs_tracing.trace_span("executor.poll") as inner:
            inner.record = False
        obs_tracing.trace_event("lifecycle.cancel")
    assert fresh_ring[-2]["psid"] == outer._sid


# -- (e) an idle cluster leaves the ring alone ------------------------------


def test_idle_cluster_adds_no_poll_records_to_the_ring(fresh_ring):
    from ballista_tpu.distributed.executor import LocalCluster

    def counts():
        totals = obs_tracing.span_totals()
        return {n: totals.get(n, {"count": 0})["count"]
                for n in ("executor.poll", "scheduler.poll_held")}

    before = counts()
    cluster = LocalCluster(num_executors=2)
    try:
        time.sleep(2.0)
        ages = [time.time() - t
                for t in cluster.state.executor_heartbeats().values()]
    finally:
        cluster.shutdown()
    polls = [r["name"] for r in fresh_ring if r["name"] in POLL_SPANS]
    assert polls == []
    # they were counted all the same, and the cluster still heartbeats:
    # two executors, each in a call the scheduler holds for an interval
    # at most, so no beat is rarer than the 250 ms sleep made it
    after = counts()
    assert after["executor.poll"] - before["executor.poll"] >= 8
    assert after["scheduler.poll_held"] - before["scheduler.poll_held"] >= 8
    assert len(ages) == 2 and max(ages) < 0.5, ages
