"""Overload-safe multi-tenant admission plane: quotas, priorities, load
shedding (docs/robustness.md "Admission & overload").

The scheduler accepted every ExecuteQuery unconditionally before this
plane: a burst of concurrent sessions could queue unbounded work,
starve each other, and blow past the budgets the metering plane
accounts per session. These tests pin the degradation ladder
(admit -> queue -> shed), the structured retryable shed contract, the
bounds on every waiting job (queue timeout, deadline, CancelJob), the
client's retry-after handling, and the overload chaos sweep: K sessions
submitting 3x cluster capacity with injected admission faults, every
admitted query byte-identical to an unloaded run, zero hangs.

Also pins the riding satellites: rate-based speculation off the live
progress samples (ROADMAP 5a), the scheduler-state leak purge at
terminal transitions, and the BALLISTA_MAX_TASK_RECOVERIES knob.

Style: service-level tests use direct calls like test_lifecycle.py;
e2e gates run a real LocalCluster.
"""

import os
import threading
import time

import numpy as np
import pandas as pd
import pytest

from ballista_tpu import Int64, Utf8, col, schema, serde, sum_
from ballista_tpu.client import BallistaContext
from ballista_tpu.distributed.admission import (
    AdmissionConfig,
    AdmissionController,
    Decision,
)
from ballista_tpu.distributed.executor import LocalCluster
from ballista_tpu.distributed.scheduler import SchedulerService
from ballista_tpu.distributed.state import MemoryBackend, SchedulerState
from ballista_tpu.distributed.types import (
    JobStatus,
    PartitionId,
    TaskStatus,
)
from ballista_tpu.errors import AdmissionRejected, QueryCancelled
from ballista_tpu.io import TblSource
from ballista_tpu.logical import LogicalPlanBuilder
from ballista_tpu.observability.progress import JobProgressTracker
from ballista_tpu.proto import ballista_pb2 as pb
from ballista_tpu.testing import faults as faults_mod
from ballista_tpu.testing.faults import reload_faults

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

TSCHEMA = schema(("a", Int64), ("c", Utf8))
GROUPBY_SQL = "select c, sum(a) as s from t group by c order by c"
N_ROWS = 120


@pytest.fixture
def faults_env():
    saved = os.environ.get("BALLISTA_FAULTS")

    def arm(spec: str):
        if spec:
            os.environ["BALLISTA_FAULTS"] = spec
        else:
            os.environ.pop("BALLISTA_FAULTS", None)
        reload_faults()

    yield arm
    if saved is None:
        os.environ.pop("BALLISTA_FAULTS", None)
    else:
        os.environ["BALLISTA_FAULTS"] = saved
    reload_faults()


def _wait_until(cond, timeout: float, msg: str):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError(msg)


def _write_tbl(tmp_path, rows: int = N_ROWS, parts: int = 2) -> str:
    d = tmp_path / "t"
    d.mkdir(exist_ok=True)
    for part in range(parts):
        lines = [f"{i}|k{i % 7}|" for i in range(rows) if i % parts == part]
        (d / f"part{part}.tbl").write_text("\n".join(lines) + "\n")
    return str(d)


def _expected(rows: int = N_ROWS) -> pd.DataFrame:
    df = pd.DataFrame({"a": range(rows),
                       "c": [f"k{i % 7}" for i in range(rows)]})
    return (df.groupby("c", as_index=False)["a"].sum()
            .rename(columns={"a": "s"})
            .sort_values("c").reset_index(drop=True))


def _assert_identical(got: pd.DataFrame, exp: pd.DataFrame):
    assert list(got.columns) == list(exp.columns)
    assert len(got) == len(exp)
    for name in exp.columns:
        g, e = got[name].to_numpy(), exp[name].to_numpy()
        assert np.array_equal(g, e), f"column {name}: {g} != {e}"


def _service() -> SchedulerService:
    return SchedulerService(SchedulerState(MemoryBackend()))


def _submit(svc, src, settings=None, deadline_secs: float = 0.0):
    plan = (LogicalPlanBuilder.scan("t", src)
            .aggregate([col("c")], [sum_(col("a")).alias("s")])
            .build())
    params = pb.ExecuteQueryParams()
    params.logical_plan.CopyFrom(serde.plan_to_proto(plan))
    for k, v in (settings or {}).items():
        params.settings[k] = v
    if deadline_secs:
        params.deadline_secs = deadline_secs
    return svc.ExecuteQuery(params)


# ---------------------------------------------------------------------------
# (a) configuration: admission.* > BALLISTA_ADMISSION_* > defaults
# ---------------------------------------------------------------------------


def test_admission_config_precedence(monkeypatch):
    # defaults: everything unlimited, enabled, bounded queue
    cfg = AdmissionConfig.from_settings({})
    assert cfg.enabled and cfg.max_session_jobs == 0
    assert cfg.max_queue_depth == 64 and cfg.queue_timeout_secs == 30.0

    # env fallback
    monkeypatch.setenv("BALLISTA_ADMISSION_MAX_SESSION_JOBS", "4")
    monkeypatch.setenv("BALLISTA_ADMISSION_QUEUE_TIMEOUT_SECS", "7.5")
    cfg = AdmissionConfig.from_settings({})
    assert cfg.max_session_jobs == 4 and cfg.queue_timeout_secs == 7.5

    # settings win over env
    cfg = AdmissionConfig.from_settings(
        {"admission.max_session_jobs": "2", "admission.enabled": "off",
         "admission.priority": "-3"})
    assert cfg.max_session_jobs == 2 and not cfg.enabled
    assert cfg.priority == -3.0

    with pytest.raises(ValueError):
        AdmissionConfig.from_settings(
            {"admission.max_running_jobs": "banana"})
    with pytest.raises(ValueError):
        AdmissionConfig.from_settings(
            {"admission.queue_timeout_secs": "-1"})


def test_gate_ladder_units():
    """The decision ladder on a bare controller: unlimited admits,
    concurrency queues, budgets/queue-full/drain shed."""
    ctl = AdmissionController(state=None)
    d = ctl.gate("j1", {"session.id": "s1"})
    assert d.action == "admit"

    # session concurrency -> queue (transient: clears when a job ends)
    s = {"session.id": "s1", "admission.max_session_jobs": "1"}
    d = ctl.gate("j2", s)
    assert d.action == "queue" and d.reason == "session-concurrency"

    # global concurrency -> queue
    d = ctl.gate("j3", {"session.id": "s2",
                        "admission.max_running_jobs": "1"})
    assert d.action == "queue" and d.reason == "cluster-concurrency"

    # releasing the active job frees the session slot
    ctl.on_terminal("j1")
    d = ctl.gate("j4", s)
    assert d.action == "admit"

    # queue full -> shed (non-transient: bounded waiting is the point)
    ctl.enqueue(ctl.gate("j5", s), args=("j5",))
    assert ctl.gate("j6", {**s, "admission.max_queue_depth": "1"}
                    ).action == "shed"

    # ... but an ADMISSIBLE submission never pays for other tenants'
    # backlog: the queue-full backstop only applies to work that would
    # actually queue
    assert ctl.gate("j6b", {"session.id": "s-free",
                            "admission.max_queue_depth": "1"}
                    ).action == "admit"
    ctl.on_terminal("j6b")

    # disabled -> everything admits
    assert ctl.gate("j7", {**s, "admission.enabled": "off"}
                    ).action == "admit"

    # draining -> shed with the structured reason
    ctl.begin_drain()
    d = ctl.gate("j8", {"session.id": "s3"})
    assert d.action == "shed" and d.reason == "draining"
    err = d.error()
    assert isinstance(err, AdmissionRejected)
    assert AdmissionRejected.parse(str(err)) == ("draining",
                                                 err.retry_after_secs)


def test_gate_bad_config_is_loud():
    """A configured-but-broken quota must fail the submission loudly,
    not silently stop being enforced (the fail-open guard is for
    INTERNAL bugs, not user config errors)."""
    ctl = AdmissionController(state=None)
    with pytest.raises(ValueError, match="admission.max_session_jobs"):
        ctl.gate("j1", {"session.id": "s",
                        "admission.max_session_jobs": "banana"})
    # nothing was reserved or recorded for the failed submission
    assert not ctl._active_session and ctl.queue_depth() == 0


def test_queue_slot_reserved_atomically_with_decision():
    """The depth check and the queue reservation are ONE critical
    section: a queue decision occupies its slot immediately (args
    pending), so racing gates cannot grow the queue past the bound."""
    ctl = AdmissionController(state=None)
    s = {"session.id": "s1", "admission.max_session_jobs": "1",
         "admission.max_queue_depth": "2"}
    ctl.gate("j1", s)  # admit
    d2 = ctl.gate("j2", s)
    assert d2.action == "queue" and ctl.queue_depth() == 1
    d3 = ctl.gate("j3", s)  # second queue BEFORE enqueue() of d2
    assert d3.action == "queue" and ctl.queue_depth() == 2
    assert ctl.gate("j4", s).action == "shed"  # bound enforced
    # args-less entries are not launchable: the pump leaves them
    ctl.on_terminal("j1")
    ctl.pump(force=True)
    assert ctl.queue_depth() == 2
    # enqueue() attaches args without duplicating the entry
    ctl.enqueue(d2, args=("j2",))
    assert ctl.queue_depth() == 2
    launched = []
    ctl.launch_fn = launched.append
    ctl.pump(force=True)
    assert launched == [("j2",)] and ctl.queue_depth() == 1


def test_launch_failure_releases_slot_and_sheds():
    """A queued job whose planning launch raises must not sit
    status=queued forever holding its slot: the slot is released and
    the job is shed as a retryable failure."""
    boom = RuntimeError("can't start new thread")

    def bad_launch(args):
        raise boom

    sheds = []
    ctl = AdmissionController(state=None, launch_fn=bad_launch,
                              shed_fn=sheds.append)
    s = {"session.id": "s1", "admission.max_session_jobs": "1"}
    ctl.gate("j1", s)
    d2 = ctl.gate("j2", s)
    ctl.enqueue(d2, args=("j2",))
    ctl.on_terminal("j1")
    ctl.pump(force=True)
    assert sheds and sheds[0].job_id == "j2"
    assert sheds[0].reason == "launch-error"
    assert not ctl._active_session, "leaked concurrency slot"


def test_terminal_race_before_admission_drops_entry():
    """A queued job cancelled before the pump admits it (the terminal
    hook ran before the entry carried args) is dropped at launch time
    and its just-reserved slot is released."""
    class FakeState:
        def __init__(self):
            self.terminal = set()

        def get_job_status(self, job_id):
            class _S:
                state = "cancelled"
            return _S() if job_id in self.terminal else None

        def ready_queue_depth(self):
            return 0

        def get_executors_metadata(self):
            return []

    st = FakeState()
    launched = []
    ctl = AdmissionController(state=st, launch_fn=launched.append)
    s = {"session.id": "s1", "admission.max_session_jobs": "1"}
    ctl.gate("j1", s)
    d2 = ctl.gate("j2", s)
    ctl.enqueue(d2, args=("j2",))
    st.terminal.add("j2")  # cancel raced: job terminal while queued
    ctl.on_terminal("j1")
    ctl.pump(force=True)
    assert launched == []
    assert not ctl._active_session, "leaked slot for terminal job"
    assert ctl.queue_depth() == 0


def test_cancel_between_retry_attempts_stops_resubmission():
    """A ctx.cancel() landing while the client sleeps between
    admission-retry attempts must stop the loop — resubmitting a query
    the user cancelled breaks the cancel contract."""
    from ballista_tpu.distributed.client import (
        CancelRequested,
        _collect_with_admission_retry,
    )

    sink: list = []
    calls = []

    def submit():
        calls.append(1)
        # simulate: submission shed, and the user cancels during the
        # retry window (ctx.cancel drops the sentinel into the sink)
        sink.append(CancelRequested("client"))
        raise AdmissionRejected("saturated", 0.05)

    with pytest.raises(QueryCancelled) as ei:
        _collect_with_admission_retry(30.0, submit,
                                      lambda jid, left: None,
                                      job_id_out=sink)
    assert ei.value.reason == "client"
    assert len(calls) == 1, "resubmitted a cancelled query"


def test_gate_session_budget_sheds(monkeypatch):
    """Cumulative session budgets read the PR 10 metering table
    (system.sessions): an exhausted budget SHEDS (queueing would never
    clear it)."""
    from ballista_tpu.observability.progress import process_session_meter

    sid = f"budget-sess-{os.getpid()}-{time.time_ns()}"
    process_session_meter().record(sid, wall_seconds=1.0,
                                   task_seconds=5.0,
                                   bytes_shuffled=1 << 20)
    ctl = AdmissionController(state=None)
    base = {"session.id": sid}
    # over the task-seconds budget
    d = ctl.gate("j1", {**base, "admission.session_task_seconds": "4"})
    assert d.action == "shed" and d.reason == "session-task-seconds"
    # over the shuffle-bytes budget
    d = ctl.gate("j2", {**base, "admission.session_shuffle_bytes":
                        str(1 << 10)})
    assert d.action == "shed" and d.reason == "session-shuffle-bytes"
    # under budget admits
    d = ctl.gate("j3", {**base, "admission.session_task_seconds": "99"})
    assert d.action == "admit"
    # another session is unaffected
    d = ctl.gate("j4", {"session.id": sid + "-other",
                        "admission.session_task_seconds": "4"})
    assert d.action == "admit"


def test_queue_ordering_priority_then_deadline():
    """Pop order: priority (higher first), then server-side deadline
    (sooner first), then arrival."""
    ctl = AdmissionController(state=None)
    now = time.time()

    def entry(job, prio=0.0, deadline=None, t=0.0):
        d = Decision("queue", job, "s",
                     config=AdmissionConfig(priority=prio),
                     deadline_ts=deadline, enqueued_at=now + t)
        ctl.enqueue(d, args=(job,))

    entry("late", t=0.2)
    entry("urgent", prio=5.0, t=0.3)
    entry("deadline-soon", deadline=now + 1.0, t=0.4)
    entry("deadline-later", deadline=now + 60.0, t=0.1)
    order = [ctl.queue_info(j)["queue_position"]
             for j in ("urgent", "deadline-soon", "deadline-later",
                       "late")]
    assert order == [1, 2, 3, 4], order


# ---------------------------------------------------------------------------
# (b) service level: queue visibility, timeout shed, cancel/deadline bounds
# ---------------------------------------------------------------------------


def test_quota_queues_with_visible_position_then_admits(tmp_path):
    svc = _service()
    src = TblSource(_write_tbl(tmp_path), TSCHEMA)
    s = {"session.id": "sess-q", "admission.max_session_jobs": "1"}
    r1 = _submit(svc, src, s)
    r2 = _submit(svc, src, s)
    assert not r1.error and not r2.error
    _wait_until(lambda: svc.state.stage_ids(r1.job_id), 10,
                "first job never planned")
    # second job is admission-queued: GetJobStatus speaks queued with
    # position/reason, /debug/jobs and system.queries agree
    gs = svc.GetJobStatus(pb.GetJobStatusParams(job_id=r2.job_id))
    assert gs.status.WhichOneof("status") == "queued"
    assert gs.status.queued.queue_position == 1
    assert gs.status.queued.reason == "session-concurrency"
    assert svc.state.stage_ids(r2.job_id) == []  # planning deferred
    jobs = {j["job_id"]: j for j in svc._debug_jobs(None)}
    assert jobs[r2.job_id]["status"] == "queued"
    assert jobs[r2.job_id]["queue_position"] == 1
    rows = {r["job_id"]: r
            for r in svc.systables.table_rows("system.queries")}
    assert rows[r2.job_id]["status"] == "queued"
    assert rows[r2.job_id]["queue_position"] == 1

    # finishing (here: cancelling) the first job frees the slot; the
    # pump launches the queued job's planning
    svc.CancelJob(pb.CancelJobParams(job_id=r1.job_id, reason="client"))
    _wait_until(lambda: svc.admission.queue_depth() == 0
                and svc.state.stage_ids(r2.job_id), 10,
                "queued job never admitted after slot freed")
    svc.CancelJob(pb.CancelJobParams(job_id=r2.job_id, reason="client"))
    svc.close_health()


def test_queue_timeout_sheds_with_structured_retryable_error(tmp_path):
    svc = _service()
    src = TblSource(_write_tbl(tmp_path), TSCHEMA)
    s = {"session.id": "sess-t", "admission.max_session_jobs": "1",
         "admission.queue_timeout_secs": "0.2",
         "admission.retry_after_secs": "2.5"}
    r1 = _submit(svc, src, s)
    r2 = _submit(svc, src, s)
    time.sleep(0.3)
    svc.admission.pump(force=True)
    gs = svc.GetJobStatus(pb.GetJobStatusParams(job_id=r2.job_id))
    assert gs.status.WhichOneof("status") == "failed"
    assert gs.status.failed.retry_after_secs == pytest.approx(2.5)
    parsed = AdmissionRejected.parse(gs.status.failed.error)
    assert parsed == ("queue-timeout", 2.5)
    # the shed observed its queue wait in the histogram
    from ballista_tpu.observability.registry import histogram_snapshot

    fam = histogram_snapshot().get(
        "ballista_admission_queue_wait_seconds", [])
    assert any(dict(labels).get("outcome") == "shed"
               for labels, *_ in fam)
    svc.CancelJob(pb.CancelJobParams(job_id=r1.job_id))
    svc.close_health()


def test_cancel_and_deadline_bound_queued_jobs(tmp_path):
    """A waiting submission is never unbounded: CancelJob removes it
    from the admission queue, and its server-side deadline holds while
    queued (the reap pass cancels it, which drops the queue entry)."""
    svc = _service()
    src = TblSource(_write_tbl(tmp_path), TSCHEMA)
    s = {"session.id": "sess-c", "admission.max_session_jobs": "1"}
    r1 = _submit(svc, src, s)
    r2 = _submit(svc, src, s)
    assert svc.admission.queue_depth() == 1
    # CancelJob on the QUEUED job: terminal cancelled, queue emptied
    res = svc.CancelJob(pb.CancelJobParams(job_id=r2.job_id,
                                           reason="client"))
    assert res.cancelled
    assert svc.admission.queue_depth() == 0
    assert svc.state.get_job_status(r2.job_id).state == "cancelled"

    # deadline on a queued job: reaped on time
    r3 = _submit(svc, src, s, deadline_secs=0.1)
    assert svc.admission.queue_depth() == 1
    time.sleep(0.15)
    svc.state.reap_expired_jobs(min_interval_secs=0.0)
    st = svc.state.get_job_status(r3.job_id)
    assert st.state == "cancelled" and st.cancel_reason == "deadline"
    assert svc.admission.queue_depth() == 0
    svc.CancelJob(pb.CancelJobParams(job_id=r1.job_id))
    svc.close_health()


def test_admission_metrics_and_trace_events(tmp_path):
    svc = _service()
    src = TblSource(_write_tbl(tmp_path), TSCHEMA)
    s = {"session.id": "sess-m", "admission.max_session_jobs": "1",
         "admission.max_queue_depth": "1"}
    _submit(svc, src, s)
    _submit(svc, src, s)  # queued
    shed = _submit(svc, src, s)  # shed: queue full
    assert shed.error
    samples = {name: v for name, labels, v in svc._metric_samples()}
    assert samples["ballista_admission_queue_depth"] == 1
    assert samples["ballista_admission_admitted_total"] == 1
    assert samples["ballista_admission_queued_total"] == 1
    assert samples["ballista_admission_sheds_total"] == 1
    # decisions landed in system.admission with the gate's reasons
    rows = svc.systables.table_rows("system.admission")
    by_decision = {}
    for r in rows:
        by_decision.setdefault(r["decision"], []).append(r)
    assert by_decision.get("admit") and by_decision.get("queue")
    assert by_decision["shed"][0]["reason"] == "queue-full"
    assert by_decision["shed"][0]["retry_after_seconds"] > 0
    # trace events fired (flight recorder is on by default)
    from ballista_tpu.observability import tracing

    names = {r.get("name") for r in tracing.ring_records()}
    assert "admission.queue" in names and "admission.shed" in names
    svc.close_health()


# ---------------------------------------------------------------------------
# (c) client contract: retry-after honored, retry can be disabled
# ---------------------------------------------------------------------------


def test_client_honors_retry_after_on_gate_shed(tmp_path, faults_env):
    """A shed submission (here: an injected admission-gate fault)
    surfaces as a structured retryable error; remote_collect sleeps the
    server's retry-after and resubmits within the job timeout — the
    query completes byte-identical."""
    path = _write_tbl(tmp_path)
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    try:
        ctx = BallistaContext("remote", "localhost", cluster.port,
                              settings={"job.timeout": "60"})
        ctx.register_tbl("t", path, TSCHEMA)
        faults_env("scheduler.admit=fail-once")
        t0 = time.time()
        out = ctx.sql(GROUPBY_SQL).collect()
        elapsed = time.time() - t0
        _assert_identical(out, _expected())
        # the armed fault genuinely fired and the client genuinely
        # waited its retry-after before resubmitting
        assert faults_mod._rules["scheduler.admit"].hits >= 1
        assert elapsed >= 0.9
        assert cluster.service.admission.sheds_total == 1
    finally:
        faults_env("")
        cluster.shutdown()


def test_client_retry_disabled_raises_immediately(tmp_path, faults_env,
                                                  monkeypatch):
    monkeypatch.setenv("BALLISTA_ADMISSION_RETRY", "off")
    path = _write_tbl(tmp_path)
    cluster = LocalCluster(num_executors=1, concurrent_tasks=1)
    try:
        ctx = BallistaContext("remote", "localhost", cluster.port,
                              settings={"job.timeout": "30"})
        ctx.register_tbl("t", path, TSCHEMA)
        faults_env("scheduler.admit=fail-once")
        with pytest.raises(AdmissionRejected) as ei:
            ctx.sql(GROUPBY_SQL).collect()
        assert ei.value.retry_after_secs > 0
        assert ei.value.reason == "admission-fault"
    finally:
        faults_env("")
        cluster.shutdown()


def test_drain_sheds_new_while_admitted_work_finishes(tmp_path,
                                                      faults_env,
                                                      monkeypatch):
    monkeypatch.setenv("BALLISTA_ADMISSION_RETRY", "off")
    path = _write_tbl(tmp_path)
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    try:
        ctx = BallistaContext("remote", "localhost", cluster.port,
                              settings={"job.timeout": "60"})
        ctx.register_tbl("t", path, TSCHEMA)
        faults_env("executor.task.start=delay:400")
        box = {}

        def run():
            try:
                box["out"] = ctx.sql(GROUPBY_SQL).collect()
            except BaseException as e:  # noqa: BLE001 - captured
                box["err"] = e

        th = threading.Thread(target=run)
        th.start()
        _wait_until(lambda: any(e._task_tokens
                                for e in cluster.executors), 10,
                    "job never started")
        cluster.service.begin_drain()
        # new work is rejected with the structured draining shed...
        ctx2 = BallistaContext("remote", "localhost", cluster.port,
                               settings={"job.timeout": "30"})
        ctx2.register_tbl("t", path, TSCHEMA)
        with pytest.raises(AdmissionRejected) as ei:
            ctx2.sql(GROUPBY_SQL).collect()
        assert ei.value.reason == "draining"
        # ...while the admitted job finishes byte-identical
        th.join(45)
        assert not th.is_alive(), "admitted job hung through drain"
        assert "err" not in box, f"admitted job failed: {box.get('err')}"
        _assert_identical(box["out"], _expected())
    finally:
        faults_env("")
        cluster.shutdown()


# ---------------------------------------------------------------------------
# (d) THE overload gate: K sessions x 3x capacity, bounds held, faults
# ---------------------------------------------------------------------------

# seed -> BALLISTA_FAULTS spec armed during the storm. Outcome law:
# every submission either completes byte-identical to the unloaded run
# or surfaces a structured retryable AdmissionRejected; configured
# bounds hold THROUGHOUT (sampled continuously); zero hangs.
OVERLOAD_SEEDS = {
    "baseline": "",
    "admit-fault": "scheduler.admit=fail-once:3",
    "queue-pump-fault": "scheduler.admission_queue=fail-once:2",
    "queue-pump-delay": "scheduler.admission_queue=delay:40",
}


@pytest.mark.parametrize("seed", sorted(OVERLOAD_SEEDS))
def test_overload_sweep_bounds_and_byte_identity(tmp_path, faults_env,
                                                 seed):
    path = _write_tbl(tmp_path)
    # capacity: 2 executors x 1 slot = 2 concurrent tasks
    cluster = LocalCluster(num_executors=2, concurrent_tasks=1)
    max_running = 2
    try:
        # unloaded control run on the SAME cluster (also warms jit)
        ctx0 = BallistaContext("remote", "localhost", cluster.port,
                               settings={"job.timeout": "60"})
        ctx0.register_tbl("t", path, TSCHEMA)
        expected = ctx0.sql(GROUPBY_SQL).collect()
        _assert_identical(expected, _expected())

        faults_env(OVERLOAD_SEEDS[seed])
        # continuous bound sampler: admitted concurrency and queue
        # depth must respect the configured bounds at every instant
        stop = threading.Event()
        observed = {"max_active": 0, "max_queue": 0, "violations": []}

        def sample():
            svc = cluster.service
            while not stop.is_set():
                active = len(svc.admission._active_session)
                depth = svc.admission.queue_depth()
                observed["max_active"] = max(observed["max_active"],
                                             active)
                observed["max_queue"] = max(observed["max_queue"], depth)
                if active > max_running:
                    observed["violations"].append(("active", active))
                time.sleep(0.01)

        sampler = threading.Thread(target=sample, daemon=True)
        sampler.start()

        # 3 sessions x 2 queries = 6 concurrent submissions = 3x the
        # 2-slot capacity; per-session quota 1, global cap max_running
        results = {}

        def run(session: int, q: int):
            settings = {
                "job.timeout": "90",
                "session.id": f"overload-{seed}-{session}",
                "admission.max_session_jobs": "1",
                "admission.max_running_jobs": str(max_running),
            }
            ctx = BallistaContext("remote", "localhost", cluster.port,
                                  settings=settings)
            ctx.register_tbl("t", path, TSCHEMA)
            try:
                results[(session, q)] = ctx.sql(GROUPBY_SQL).collect()
            except BaseException as e:  # noqa: BLE001 - captured
                results[(session, q)] = e

        threads = [threading.Thread(target=run, args=(s, q))
                   for s in range(3) for q in range(2)]
        t0 = time.time()
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        stop.set()
        sampler.join(2)
        hung = [th for th in threads if th.is_alive()]
        assert not hung, \
            f"seed {seed}: {len(hung)} submissions HUNG after " \
            f"{time.time() - t0:.0f}s"

        completions = 0
        for key, out in sorted(results.items()):
            if isinstance(out, pd.DataFrame):
                _assert_identical(out, expected)
                completions += 1
            else:
                # the only acceptable error is the structured
                # retryable shed
                assert isinstance(out, AdmissionRejected), \
                    f"seed {seed} {key}: dirty failure " \
                    f"{type(out).__name__}: {out}"
                assert out.retry_after_secs > 0
        assert completions >= 4, \
            f"seed {seed}: only {completions}/6 completed"
        assert not observed["violations"], observed["violations"]
        assert observed["max_active"] <= max_running
        assert observed["max_queue"] <= 64
        # quiesced: no leaked queue entries or session slots
        assert cluster.service.admission.queue_depth() == 0
        _wait_until(
            lambda: not cluster.service.admission._active_session, 10,
            "admitted-job bookkeeping never drained")
    finally:
        faults_env("")
        cluster.shutdown()


def test_overload_queue_full_sheds_are_structured(tmp_path,
                                                  monkeypatch,
                                                  faults_env):
    """With a 1-deep queue and retry disabled, the overflow submission
    of a 3-burst single-session storm is shed queue-full; the other two
    complete byte-identical."""
    monkeypatch.setenv("BALLISTA_ADMISSION_RETRY", "off")
    path = _write_tbl(tmp_path)
    cluster = LocalCluster(num_executors=2, concurrent_tasks=1)
    try:
        settings = {
            "job.timeout": "60",
            "session.id": "storm-sess",
            "admission.max_session_jobs": "1",
            "admission.max_queue_depth": "1",
        }
        ctx0 = BallistaContext("remote", "localhost", cluster.port,
                               settings={"job.timeout": "60"})
        ctx0.register_tbl("t", path, TSCHEMA)
        expected = ctx0.sql(GROUPBY_SQL).collect()
        # slow tasks, so the burst overlaps whatever the hand-off takes:
        # the first job still runs when the third arrives
        faults_env("executor.task.start=delay:400")

        results = {}

        def run(i):
            ctx = BallistaContext("remote", "localhost", cluster.port,
                                  settings=dict(settings))
            ctx.register_tbl("t", path, TSCHEMA)
            try:
                results[i] = ctx.sql(GROUPBY_SQL).collect()
            except BaseException as e:  # noqa: BLE001 - captured
                results[i] = e
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(3)]
        for th in threads:
            th.start()
            time.sleep(0.05)  # deterministic arrival order
        for th in threads:
            th.join(90)
        assert all(not th.is_alive() for th in threads), "storm hung"
        sheds = [r for r in results.values()
                 if isinstance(r, AdmissionRejected)]
        oks = [r for r in results.values()
               if isinstance(r, pd.DataFrame)]
        assert len(sheds) == 1 and len(oks) == 2, results
        assert sheds[0].reason == "queue-full"
        assert sheds[0].retry_after_secs > 0
        for out in oks:
            _assert_identical(out, expected)
    finally:
        cluster.shutdown()


# ---------------------------------------------------------------------------
# (e) satellite: rate-based speculation off the live progress samples
# ---------------------------------------------------------------------------


def _running_job(state, n_tasks=2, started_ago=5.0):
    state.save_job_status("j1", JobStatus("running"))
    state.save_stage_plan("j1", 1, b"", n_tasks, [])
    now = time.time()
    for p in range(n_tasks):
        state.save_task_status(TaskStatus(
            PartitionId("j1", 1, p), "running", executor_id=f"e{p}",
            started_at=now - started_ago))


def _report(tracker, pid, rows, elapsed):
    tracker.record_report("j1", 1, pid, {
        "rows_so_far": rows, "input_rows_total": 10000,
        "bytes_so_far": 0, "elapsed_seconds": elapsed,
        "operator": "Scan", "stage_version": 0})


def test_speculation_rate_trigger_beats_age():
    """A task whose sampled rate trails the stage median by the lag
    factor is duplicated BEFORE the wall-clock age trigger would fire
    (ROADMAP 5a: the scheduler consumes the progress model)."""
    state = SchedulerState(MemoryBackend())
    tracker = JobProgressTracker(state=state)
    tracker.register_job("j1")
    _running_job(state, n_tasks=3, started_ago=5.0)  # well under age 60
    _report(tracker, 0, rows=20, elapsed=5.0)    # 4 rows/s: straggler
    _report(tracker, 1, rows=2000, elapsed=5.0)  # 400 rows/s
    _report(tracker, 2, rows=1800, elapsed=5.0)  # 360 rows/s
    assert tracker.is_lagging("j1", 1, 0) is True
    assert tracker.is_lagging("j1", 1, 1) is False
    pid = state.speculative_task(age_secs=60.0, executor_id="other",
                                 min_interval_secs=0.0,
                                 lag_fn=tracker.speculation_lag_fn())
    assert pid == PartitionId("j1", 1, 0)
    # at most one duplicate per task; its healthy siblings are NOT
    # speculated even when old (a measured not-lagging verdict wins
    # over the age trigger)
    for t in state.get_task_statuses("j1", 1):
        t.started_at = time.time() - 120.0
        state.save_task_status(t)
    assert state.speculative_task(age_secs=60.0, executor_id="other",
                                  min_interval_secs=0.0,
                                  lag_fn=tracker.speculation_lag_fn()) \
        is None


def test_speculation_age_fallback_without_samples():
    """No samples (progress plane off / task outran the heartbeat):
    the old wall-clock age trigger still speculates."""
    state = SchedulerState(MemoryBackend())
    tracker = JobProgressTracker(state=state)
    tracker.register_job("j1")
    _running_job(state, n_tasks=2, started_ago=120.0)
    pid = state.speculative_task(age_secs=60.0, executor_id="other",
                                 min_interval_secs=0.0,
                                 lag_fn=tracker.speculation_lag_fn())
    assert pid is not None
    # and a young task with no samples is left alone
    state2 = SchedulerState(MemoryBackend())
    _running_job(state2, n_tasks=2, started_ago=5.0)
    assert state2.speculative_task(age_secs=60.0, executor_id="other",
                                   min_interval_secs=0.0,
                                   lag_fn=None) is None


def test_speculation_lag_factor_knob(monkeypatch):
    from ballista_tpu.observability.progress import \
        speculation_lag_factor

    assert speculation_lag_factor() == 3.0
    monkeypatch.setenv("BALLISTA_SPECULATION_LAG_FACTOR", "10")
    assert speculation_lag_factor() == 10.0
    monkeypatch.setenv("BALLISTA_SPECULATION_LAG_FACTOR", "junk")
    assert speculation_lag_factor() == 3.0
    # factor <= 1 disables the rate trigger entirely
    monkeypatch.setenv("BALLISTA_SPECULATION_LAG_FACTOR", "1")
    state = SchedulerState(MemoryBackend())
    tracker = JobProgressTracker(state=state)
    tracker.register_job("j1")
    _running_job(state, n_tasks=2, started_ago=5.0)
    _report(tracker, 0, rows=1, elapsed=5.0)
    _report(tracker, 1, rows=5000, elapsed=5.0)
    assert tracker.is_lagging("j1", 1, 0) is None


# ---------------------------------------------------------------------------
# (f) satellites: state leak purge + retry-budget knob
# ---------------------------------------------------------------------------


def test_terminal_transition_purges_speculation_and_recovery_state():
    """_speculated / _spec_failed_once / recoveries/<job> grew for the
    scheduler's lifetime before this PR; the terminal transition now
    cleans them (pinning the leak fix)."""
    state = SchedulerState(MemoryBackend())
    for jid, final in (("j1", "completed"), ("j2", "failed"),
                       ("j3", "cancelled")):
        state.save_job_status(jid, JobStatus("queued"))
        pid = PartitionId(jid, 1, 0)
        with state._lock:
            state._speculated.add(pid)
            state._spec_failed_once.add(pid)
        state._bump_recovery(jid)
        assert state._recovery_count(jid) == 1
    # an UNRELATED live job's state must survive the purges
    live_pid = PartitionId("j-live", 1, 0)
    with state._lock:
        state._speculated.add(live_pid)
        state._spec_failed_once.add(live_pid)
    state._bump_recovery("j-live")

    state.save_job_status("j1", JobStatus("completed"))
    state.save_job_status("j2", JobStatus("failed", error="boom"))
    state.cancel_job("j3", "client")
    with state._lock:
        assert state._speculated == {live_pid}
        assert state._spec_failed_once == {live_pid}
    for jid in ("j1", "j2", "j3"):
        assert state._recovery_count(jid) == 0
        assert state.kv.get(state._k("recoveries", jid)) is None
    assert state._recovery_count("j-live") == 1


def test_max_recoveries_knob(monkeypatch):
    state = SchedulerState(MemoryBackend())
    assert state.MAX_RECOVERIES_PER_JOB == 3
    monkeypatch.setenv("BALLISTA_MAX_TASK_RECOVERIES", "1")
    assert state.MAX_RECOVERIES_PER_JOB == 1
    # the budget is READ per recovery decision: one transient failure
    # recovers, the second fails the job under budget 1
    state.save_job_status("jr", JobStatus("running"))
    state.save_stage_plan("jr", 1, b"", 1, [])
    st = TaskStatus(PartitionId("jr", 1, 0), "failed",
                    error="IoError: flaky")
    assert state.recover_transient_failure(st) is True
    assert state.recover_transient_failure(st) is False
    monkeypatch.setenv("BALLISTA_MAX_TASK_RECOVERIES", "junk")
    assert state.MAX_RECOVERIES_PER_JOB == 3


def test_scheduler_binary_sigterm_drains():
    """The REAL scheduler binary's SIGTERM path: the launcher catches
    the signal with a process-wide handler (config.install_stop_signals)
    — ``import jax`` starts threads before main() runs, so a mask +
    sigwait let the signal land on an unmasked thread and take the
    default disposition (exit -15), and the drain rung never ran. The
    executor binary shares the helper."""
    import signal
    import subprocess
    import sys

    p = subprocess.Popen(
        [sys.executable, "-m", "ballista_tpu.distributed.scheduler_main",
         "--port", "0", "--flight-port", "-1", "--metrics-port", "-1"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        deadline = time.time() + 60
        while time.time() < deadline:
            if "listening on" in p.stdout.readline():
                break
        p.send_signal(signal.SIGTERM)
        out, _ = p.communicate(timeout=40)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, f"rc={p.returncode}: {out}"
    assert "draining (new submissions are shed)" in out, out


# ---------------------------------------------------------------------------
# (g) bench_serving smoke: the serving bench emits its gated fields
# ---------------------------------------------------------------------------


def test_bench_serving_emits_gated_fields(tmp_path_factory):
    """bench_serving.run_serving end-to-end on tiny data with a tiny
    mix: the JSON fields dev/check_bench_regress.py gates must be
    populated and self-consistent (a broken serving bench must fail
    here, not silently record zeros into BENCH_rNN.json)."""
    import sys

    sys.path.insert(0, REPO)
    from benchmarks.tpch import datagen
    import bench_serving

    data_dir = str(tmp_path_factory.mktemp("serving_smoke"))
    datagen.generate(data_dir, scale=0.002, num_parts=2)
    out = bench_serving.run_serving(
        data_dir, sessions=2, queries_per_session=1, executors=2,
        slots=1, max_running=2, session_quota=1, job_timeout=120.0,
        mix=("q1",))
    assert out["metric"] == "serving_qps" and out["value"] > 0
    assert out["serving_completed"] == 2
    assert out["serving_errors"] == 0
    assert out["serving_p50_seconds"] > 0
    assert out["serving_p99_seconds"] >= out["serving_p50_seconds"]
    assert out["serving_admitted"] >= 2
    assert out["serving_solo_seconds"]["q1"] > 0


# ---------------------------------------------------------------------------
# (h) overhead gate: the admission hot path costs < 5% on submissions
# ---------------------------------------------------------------------------


def test_admission_overhead_under_5pct(tmp_path):
    """Drift-cancelling gate on the hot path admission actually sits on
    (ExecuteQuery -> planned): submissions with the gate evaluating
    real (non-binding) quotas vs admission.enabled=off, interleaved
    alternating samples + medians, <5% (+2ms floor) or fail."""
    svc = _service()
    src = TblSource(_write_tbl(tmp_path, rows=8, parts=1), TSCHEMA)
    on_settings = {"session.id": "ovh", "admission.max_session_jobs":
                   "64", "admission.max_running_jobs": "64"}
    off_settings = {"session.id": "ovh", "admission.enabled": "off"}

    def cycle(settings):
        r = _submit(svc, src, settings)
        assert not r.error
        deadline = time.time() + 10
        while not svc.state.stage_ids(r.job_id):
            assert time.time() < deadline, "planning never finished"
            time.sleep(0.001)
        svc.CancelJob(pb.CancelJobParams(job_id=r.job_id))

    def sample(on: bool) -> float:
        t0 = time.perf_counter()
        for _ in range(3):
            cycle(on_settings if on else off_settings)
        return time.perf_counter() - t0

    sample(True)
    sample(False)  # settle both paths

    def measure():
        offs, ons = [], []
        for i in range(9):
            if i % 2 == 0:
                offs.append(sample(False))
                ons.append(sample(True))
            else:
                ons.append(sample(True))
                offs.append(sample(False))
        return sorted(offs)[4], sorted(ons)[4]

    try:
        for _ in range(3):
            t_off, t_on = measure()
            if t_on <= t_off * 1.05 + 2e-3:
                return
        overhead = (t_on - t_off) / t_off
        raise AssertionError(
            f"admission overhead {overhead:.1%} "
            f"(on={t_on:.4f}s off={t_off:.4f}s)")
    finally:
        svc.close_health()
