"""Executor: pull-based worker running stage tasks on the local device(s).

Re-implements the reference executor (reference: rust/executor/src/
execution_loop.rs:31-160 poll loop, flight_service.rs:89-192 partition
execution + IPC materialization, main.rs --local embedded-scheduler mode).
Improvements over the reference by design:

- tasks execute in-process (the reference self-RPCs its own Flight port,
  execution_loop.rs:90-101, and calls that "convoluted" itself);
- the data plane is a socket server (Python or the C++ native
  shuffle_server) serving the same work_dir layout.
"""

from __future__ import annotations

import logging
import os
import random
import shutil
import tempfile
import threading
import time
import uuid
from collections import deque
from concurrent import futures
from typing import Dict, Optional

from ..errors import QueryCancelled
from ..lifecycle import CancelToken, bind_token, check_cancel
from ..observability import trace_event, trace_span
from ..observability.metrics import collect_plan_metrics, metrics_enabled
from ..proto import ballista_pb2 as pb
from .. import serde
from ..testing.faults import fault_point
from .dataplane import partition_path, start_data_plane
from .scheduler import SchedulerClient
from .types import PartitionId

log = logging.getLogger("ballista.executor")

# heartbeat and fallback: every wait of the hand-off ends on the event it
# waits for (a task ends, a slot is free, a task becomes ready); this is
# how long one lasts when no event ends it (reference: 250ms,
# execution_loop.rs:41, where it is the only way a wait ends)
POLL_INTERVAL_SECS = 0.25
# total task-profile bytes one PollWork may carry (well under the
# transport's raised 64 MB cap; see scheduler._GRPC_MSG_OPTS)
_POLL_PROFILE_BUDGET_BYTES = 8 << 20


def _poll_backoff_max_secs() -> float:
    """Poll-loop backoff ceiling while the scheduler is unreachable."""
    try:
        return max(float(os.environ.get(
            "BALLISTA_POLL_BACKOFF_MAX_SECS", "8") or 8), POLL_INTERVAL_SECS)
    except ValueError:
        return 8.0


def drain_timeout_secs() -> float:
    """``BALLISTA_DRAIN_TIMEOUT_SECS``: how long a graceful drain lets
    in-flight tasks finish before cancelling them."""
    try:
        return max(float(os.environ.get(
            "BALLISTA_DRAIN_TIMEOUT_SECS", "20") or 20), 0.0)
    except ValueError:
        return 20.0


def _needs_mesh(plan) -> bool:
    """True when the plan contains a mesh-fused operator (its SPMD
    program must run on every process of a mesh group)."""
    from ..physical.mesh_agg import MeshAggExec, MeshJoinExec

    if isinstance(plan, (MeshAggExec, MeshJoinExec)):
        return True
    return any(_needs_mesh(c) for c in plan.children())


class ExecutorConfig:
    """(reference: executor_config_spec.toml:1-61)"""

    def __init__(self, host: str = "localhost", port: int = 0,
                 work_dir: Optional[str] = None, concurrent_tasks: int = 2,
                 scheduler_host: str = "localhost",
                 scheduler_port: int = 50050,
                 bind_host: Optional[str] = None,
                 num_devices: int = 1,
                 native_dataplane: Optional[bool] = None,
                 metrics_port: Optional[int] = None):
        # host = the address peers should dial (advertised in PollWork);
        # bind_host = the local interface the data plane listens on.
        # Distinct so NAT/port-forward setups can bind 0.0.0.0 while
        # advertising an external address.
        self.host = host
        self.bind_host = bind_host if bind_host is not None else host
        # None = resolve from BALLISTA_NATIVE_DATAPLANE (default: native)
        self.native_dataplane = native_dataplane
        self.port = port
        # devices this executor owns (reported in PollWork metadata;
        # mesh fusion is driven by these fleet reports — a client
        # mesh.devices setting is only validated against them)
        self.num_devices = num_devices
        self.work_dir = work_dir or tempfile.mkdtemp(prefix="ballista-")
        self.concurrent_tasks = concurrent_tasks
        self.scheduler_host = scheduler_host
        self.scheduler_port = scheduler_port
        # health plane port: None = resolve BALLISTA_METRICS_PORT
        # (default off for in-process executors; the binary defaults it
        # to 0 = ephemeral ON); < 0 disables
        self.metrics_port = metrics_port


class Executor:
    def __init__(self, config: ExecutorConfig, mesh_group=None):
        self.config = config
        # mesh_group: a mesh_group.GroupLeader when this executor fronts
        # a multi-process device mesh; fused tasks are broadcast so
        # every member enters the SPMD program together
        self.mesh_group = mesh_group
        self.id = str(uuid.uuid4())
        # distributed profiler: stamp this process's identity onto every
        # trace/flight-recorder record (first writer wins — harmless for
        # in-process LocalClusters, where per-task window extraction
        # re-tags records with the owning executor's id instead)
        from ..observability.tracing import set_process_identity

        set_process_identity("executor", self.id)
        self._data_plane = start_data_plane(
            config.bind_host, config.port, config.work_dir,
            native=config.native_dataplane,
        )
        self.port = self._data_plane.port
        self._client = SchedulerClient(config.scheduler_host,
                                       config.scheduler_port)
        self._pool = futures.ThreadPoolExecutor(
            max_workers=config.concurrent_tasks
        )
        self._slots = threading.Semaphore(config.concurrent_tasks)
        self._status_lock = threading.Lock()
        self._pending_status = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # event-driven hand-off: a task that ends sets _task_ended
        # (after its slot is free) and the report thread polls at once,
        # so the report never waits for the timer nor behind the poll
        # thread's call, which the scheduler may be holding; once the
        # report thread is done and a slot is free it sets _slot_free,
        # which ends the poll thread's wait so that it offers the slot
        # in a call the scheduler can hold. _asking counts polls in
        # flight that offered a slot (under _token_lock): a drain waits
        # for them, each may still bring a task.
        self._task_ended = threading.Event()
        self._slot_free = threading.Event()
        self._report_thread: Optional[threading.Thread] = None
        self._asking = 0
        # lifecycle control plane: one cancel token per active task
        # (registered BEFORE the pool accepts the work so drain sees
        # queued-but-unstarted tasks too), the draining flag PollWork
        # advertises as can_accept_task=False, and a bounded memory of
        # job ids whose partial outputs were already cleaned
        self._token_lock = threading.Lock()
        self._task_tokens: Dict[str, CancelToken] = {}  # task key -> token
        self._draining = False
        self._cleaned_jobs: deque = deque(maxlen=256)
        # live progress plane: the executing plan of every in-flight
        # task, sampled on the progress cadence and piggybacked on
        # PollWork as TaskProgress records (best-effort; see
        # observability/progress.py)
        self._progress_lock = threading.Lock()
        self._running_plans: Dict[str, dict] = {}  # task key -> entry
        self._last_progress_sample = 0.0
        # health plane: task counters (benign-race ints under the GIL,
        # same policy as observability.metrics), a ring of recent task
        # summaries, and — when enabled — /healthz + /metrics +
        # /debug/queries on a local stdlib HTTP server
        self._inflight = 0
        self.tasks_completed = 0
        self.tasks_failed = 0
        self.tasks_cancelled = 0
        from ..observability.health import (QueryLog,
                                            maybe_start_health_server,
                                            metrics_port_from_env)

        self._query_log = QueryLog()
        mport = config.metrics_port
        if mport is None:
            mport = metrics_port_from_env(-1)
        self._health = maybe_start_health_server(
            "executor", mport, samples_fn=self._metric_samples,
            query_log=self._query_log,
        )

    @property
    def health_port(self) -> Optional[int]:
        return self._health.port if self._health is not None else None

    def resource_gauges(self) -> dict:
        """Current resource gauges: shipped with every heartbeat and
        exported on the local /metrics."""
        from ..ingest import pool_queue_depth
        from ..observability import memory as obs_memory
        from . import spill as _spill

        gov = _spill.governor().stats()
        return {
            "rss_bytes": obs_memory.rss_bytes(),
            "device_bytes": obs_memory.device_bytes(),
            # clamped: the counter is a benign-race int (same policy as
            # the task counters), but a lost update must never drive a
            # negative into the uint32 proto field — that would make
            # every subsequent heartbeat raise and starve the executor
            "inflight_tasks": max(0, self._inflight),
            "ingest_pool_depth": pool_queue_depth(),
            "peak_host_bytes": obs_memory.peak_host_bytes(),
            # shuffle memory governor: in-flight buffer bytes + bytes
            # spilled to disk, so the scheduler sees memory pressure
            # per executor
            "shuffle_inflight_bytes": gov["inflight_bytes"],
            "spill_bytes_total": gov["spilled_bytes_total"],
        }

    def _metric_samples(self):
        # only the executor-specific gauges: rss/device/peak are
        # appended by the health server's base process samples — going
        # through resource_gauges() here would sample them twice per
        # scrape
        from ..ingest import pool_queue_depth

        return [
            ("ballista_inflight_tasks", {}, max(0, self._inflight)),
            ("ballista_ingest_pool_depth", {}, pool_queue_depth()),
            ("ballista_tasks_completed_total", {}, self.tasks_completed),
            ("ballista_tasks_failed_total", {}, self.tasks_failed),
            ("ballista_tasks_cancelled_total", {}, self.tasks_cancelled),
        ]

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        self._thread = threading.Thread(
            target=self._poll_loop, daemon=True, name=f"poll-{self.id[:8]}"
        )
        self._thread.start()
        self._report_thread = threading.Thread(
            target=self._report_loop, daemon=True,
            name=f"report-{self.id[:8]}"
        )
        self._report_thread.start()

    def stop(self, drain: bool = False,
             drain_timeout: Optional[float] = None):
        """Stop the executor. ``drain=False`` (default) keeps the old
        immediate-shutdown behavior: running tasks are abandoned
        mid-flight. ``drain=True`` is the graceful path: stop accepting
        (PollWork advertises ``can_accept_task=False``), give in-flight
        tasks up to the drain bound to finish, cancel whatever is still
        running (their failure reports are transient-shaped, so the
        scheduler re-queues them elsewhere), and flush
        ``_pending_status`` in one final poll so completion reports are
        never lost."""
        if drain:
            self._drain(drain_timeout)
        self._stop.set()
        self._task_ended.set()
        self._slot_free.set()
        for t in (self._thread, self._report_thread):
            if t:
                t.join(timeout=5)
        if drain:
            # final flush AFTER the poll threads stopped: whatever
            # reports the last in-flight tasks appended still reach the
            # scheduler even though no more polls will run
            try:
                self._flush_status()
            except Exception:  # noqa: BLE001 - best-effort on shutdown
                log.warning("final status flush failed", exc_info=True)
        self._data_plane.close()
        self._pool.shutdown(wait=False)
        # release device-resident table-cache pins: a stopped executor
        # must not keep device memory pinned while the process lingers
        # (embedding tests / LocalCluster reuse the same process)
        try:
            from ..cache.residency import process_table_cache

            process_table_cache().invalidate()
        except Exception:  # noqa: BLE001 - best-effort on shutdown
            pass
        if self._health is not None:
            self._health.close()

    def _drain(self, drain_timeout: Optional[float]):
        bound = (drain_timeout if drain_timeout is not None
                 else drain_timeout_secs())
        self._draining = True
        deadline = time.time() + bound
        log.info("draining executor %s: %d active task(s), bound %.1fs",
                 self.id[:8], len(self._task_tokens), bound)
        # a poll in flight that offered a slot (the scheduler may hold
        # it for up to an interval) can still bring a task: in flight too
        while time.time() < deadline and (self._task_tokens
                                          or self._asking):
            time.sleep(0.05)
        leftover = self._fire_tokens(reason="drain")
        if leftover:
            log.warning("drain bound hit; cancelled %d in-flight task(s)",
                        leftover)
            # cooperative aborts land at the next batch boundary; give
            # them a short grace so their failure reports make the
            # final flush
            grace = time.time() + 5.0
            while time.time() < grace and self._task_tokens:
                time.sleep(0.05)

    def _fire_tokens(self, reason: str,
                     job_id: Optional[str] = None) -> int:
        """Fire the cancel tokens of active tasks (all, or one job's);
        returns how many were fired."""
        with self._token_lock:
            tokens = [t for t in self._task_tokens.values()
                      if job_id is None or t.job_id == job_id]
        n = 0
        for t in tokens:
            if t.cancel(reason):
                n += 1
        return n

    def _flush_status(self):
        """One synchronous PollWork carrying only pending reports (no
        task request): the drain path's last word to the scheduler."""
        with self._status_lock:
            pending = list(self._pending_status)
            self._pending_status.clear()
        if not pending:
            return
        params = pb.PollWorkParams(can_accept_task=False)
        params.metadata.id = self.id
        params.metadata.host = self.config.host
        params.metadata.port = self.port
        params.metadata.num_devices = self.config.num_devices
        for st in pending:
            # profiles are advisory payload; the final flush is about
            # never losing the REPORTS
            if st.HasField("completed") and st.completed.HasField("profile"):
                self._drop_profile(st)
            params.task_status.append(st)
        self._client.PollWork(params)

    # -- poll loops (reference: execution_loop.rs:31-76) ---------------------
    #
    # The hand-off has four waits, and each ends on the event it waits
    # for; POLL_INTERVAL_SECS is only the heartbeat and the fallback:
    # - a finished task's report: the task sets _task_ended once its slot
    #   is free and the report thread polls at once (executor.report_now);
    # - a free slot after a hand-out: whichever thread was handed a task
    #   asks again at once (executor.refill);
    # - an idle slot: the poll thread offers it in a call that lets the
    #   scheduler hold it until a task becomes ready (wait_secs);
    # - the client's wait is the scheduler's (GetJobStatusParams.wait_secs).
    # Reports never travel behind a held call: the report thread's calls
    # are not held, and both threads carry whatever is pending.

    def _poll_loop(self):
        """The timer's thread: one poll an interval as heartbeat and
        fallback, sooner when the last one brought a task, or when the
        report thread says a slot is free that no held call offers yet;
        the jittered back-off while the scheduler is unreachable."""
        failures = 0
        backoff = 0.0
        while not self._stop.is_set():
            began = time.monotonic()
            self._slot_free.clear()
            try:
                again = self._poll_once(hold=True)
            except Exception as e:  # noqa: BLE001 - retry like reference
                # jittered exponential backoff (reset on success): a
                # scheduler restart must not face a thundering herd of
                # fixed-interval retries, and a down scheduler must not
                # fill the log with one traceback per 250ms
                failures += 1
                backoff = min(max(backoff * 2, POLL_INTERVAL_SECS),
                              _poll_backoff_max_secs())
                wait = backoff * (1.0 + 0.25 * random.random())
                if failures == 1:
                    log.exception("poll failed; backing off")
                else:
                    log.warning(
                        "poll still failing (%d consecutive; %s: %s); "
                        "next retry in %.2fs", failures,
                        type(e).__name__, e, wait)
                self._poll_wait(wait, self._stop)
                continue
            if failures:
                log.info("scheduler reachable again after %d failed "
                         "poll(s)", failures)
            failures = 0
            backoff = 0.0
            if not again:
                self._poll_wait(
                    POLL_INTERVAL_SECS - (time.monotonic() - began),
                    self._slot_free)

    def _report_loop(self):
        """The events' thread: polls the moment a task of this executor
        has ended, so its report leaves within a round trip and the
        reply can bring the next stage's task, then again while a poll
        brought a task and a slot is still free. These calls are never
        held. One that fails leaves its reports (re-fronted) to the
        timer's thread, which owns retry and back-off."""
        while True:
            self._task_ended.wait()
            if self._stop.is_set():
                return
            self._task_ended.clear()
            trace_event("executor.report_now", executor=self.id[:8])
            try:
                while self._poll_once():
                    pass
            except Exception as e:  # noqa: BLE001 - the timer retries
                log.warning("report poll failed (%s: %s); the next timed "
                            "poll re-delivers", type(e).__name__, e)
            if self._has_free_slot():
                self._slot_free.set()

    def _has_free_slot(self) -> bool:
        free = self._slots.acquire(blocking=False)
        if free:
            self._slots.release()
        return free

    def _poll_wait(self, seconds: float, until: threading.Event):
        """What is left of the interval after a poll (all of it when
        the scheduler did not hold the call), unless ``until`` is set
        first, as an ``executor.poll_wait`` span. With no task in flight
        and no report pending when it ends, the span stays out of the
        flight recorder (profiler annotation and span totals only): an
        idle cluster must not turn the ring over with its waits."""
        if seconds <= 0:
            return
        # the oldest report pending as the wait begins (reports leave
        # from the front, in order)
        oldest = next(iter(self._pending_status), None)
        with trace_span("executor.poll_wait",
                        executor=self.id[:8]) as span:
            ended_early = until.wait(seconds)
            late = bool(self._pending_status)
            span.record = self._inflight > 0 or late
        if oldest is not None and not ended_early and \
                next(iter(self._pending_status), None) is oldest:
            # THAT report sat out a whole wait that no event ended: the
            # report thread's poll failed (should read 0 a query). One
            # filed while this wait ran is the report thread's to send,
            # and is on its way when the wait times out beside it
            trace_event("executor.report_waited", executor=self.id[:8])

    def _drop_profile(self, st) -> None:
        """Send a completion report without its profile window, and
        count it (``span_totals()["executor.profile_dropped"]``): with
        the window go the task's ledger deltas, ``report_wait`` among
        them."""
        st.completed.ClearField("profile")
        pid = st.partition_id
        trace_event("executor.profile_dropped", executor=self.id[:8],
                    task=f"{pid.job_id}/{pid.stage_id}/{pid.partition_id}")

    def _poll_once(self, hold: bool = False) -> bool:
        """One ``PollWork`` round trip: heartbeat, every pending report,
        and a slot offered if one is free. ``hold`` (the timer's thread)
        lets the scheduler keep the call until a task becomes ready, if
        it offers a slot and carries no report. Returns True when the
        caller should ask again at once: a slot is free, and either the
        reply brought a task (``executor.refill``) or this was the
        timer's call and could not be held."""
        # the slot this poll offers stays claimed while the call is in
        # flight: both threads may be asking, and together they must
        # never be handed more tasks than there are slots. (A draining
        # executor finishes what is in flight and offers nothing.)
        offered = not self._draining and \
            self._slots.acquire(blocking=False)
        if offered:
            with self._token_lock:
                self._asking += 1
        started = False
        try:
            td, holdable = self._exchange(offered, hold)
            if self._stop.is_set():
                td = None  # stopped while the call was held: abandon it
            if td is not None:
                if not offered:
                    # a task nobody asked for: it waits for a slot, as
                    # it did when every hand-out did
                    self._slots.acquire()
                started = True
                self._run_task(td)
        finally:
            if offered:
                if not started:
                    self._slots.release()
                with self._token_lock:
                    self._asking -= 1
        if self._draining or not self._has_free_slot():
            return False
        if td is not None:
            trace_event("executor.refill", executor=self.id[:8])
            return True
        return hold and not holdable

    def _exchange(self, offered: bool, hold: bool):
        """The round trip of ``_poll_once``: returns the task the reply
        brought, if any, and whether the scheduler could hold the
        call."""
        params = pb.PollWorkParams(can_accept_task=offered)
        params.metadata.id = self.id
        params.metadata.host = self.config.host
        params.metadata.port = self.port
        params.metadata.num_devices = self.config.num_devices
        # heartbeat resource gauges: the scheduler aggregates these
        # into its own /metrics (per-executor labels)
        g = self.resource_gauges()
        params.metadata.resources.rss_bytes = int(g["rss_bytes"])
        params.metadata.resources.device_bytes = int(g["device_bytes"])
        params.metadata.resources.inflight_tasks = int(g["inflight_tasks"])
        params.metadata.resources.ingest_pool_depth = \
            int(g["ingest_pool_depth"])
        params.metadata.resources.peak_host_bytes = \
            int(g["peak_host_bytes"])
        params.metadata.resources.shuffle_inflight_bytes = \
            int(g["shuffle_inflight_bytes"])
        params.metadata.resources.spill_bytes_total = \
            int(g["spill_bytes_total"])
        with self._status_lock:
            pending = list(self._pending_status)
            self._pending_status.clear()
        # profile windows are advisory observability payload: bound what
        # one poll ships so a burst of completions (each profile up to
        # 512 KiB) can never push the request past the transport's
        # message limit — a failed PollWork would LOSE the completion
        # reports it carried (pending was already cleared) and hang the
        # job. Reports always go; overflow profiles are dropped.
        holdable = hold and offered and not pending
        if holdable:
            params.wait_secs = POLL_INTERVAL_SECS
        budget = _POLL_PROFILE_BUDGET_BYTES
        sending = time.time()
        for st in pending:
            if st.HasField("completed") and st.completed.HasField("profile"):
                self._stamp_report_wait(st, sending)
                sz = st.completed.profile.ByteSize()
                if sz > budget:
                    self._drop_profile(st)
                else:
                    budget -= sz
            params.task_status.append(st)
        # live progress piggyback: advisory payload, never re-delivered
        # on a failed poll (unlike the reports above — the next sample
        # supersedes a lost one anyway)
        for tp in self._maybe_sample_progress():
            params.task_progress.append(tp)
        try:
            # the round trip itself; kept out of the flight recorder
            # when it carried no report and brought no task
            with trace_span("executor.poll", executor=self.id[:8],
                            reports=len(pending)) as span:
                result = self._client.PollWork(params)
                span.record = bool(pending) or result.HasField("task")
        except Exception:
            # report re-delivery: a failed poll (scheduler down, RPC
            # fault) must not LOSE the completion/failure reports it
            # carried — without them the scheduler only recovers the
            # tasks via lease reaping or speculation, minutes later.
            # Re-front them so the next successful poll delivers
            # (profiles already stripped above stay stripped: advisory)
            with self._status_lock:
                self._pending_status[:0] = pending
            raise
        for job_id in result.cancelled_jobs:
            self._handle_job_cancelled(job_id)
        if result.released_jobs:
            # the client has these jobs' results: nothing will read their
            # files again. Off this thread: the next hand-off must not
            # wait for the unlinks (1.1 GB a q3 at SF10)
            threading.Thread(
                target=self._remove_released, daemon=True,
                args=(list(result.released_jobs),)).start()
        if result.drain and not self._draining:
            # autoscaler scale-down piggyback: stop accepting work; the
            # poll loop keeps reporting until in-flight tasks finish
            # (executor_main exits on its own drain path afterwards)
            log.warning("executor %s: scheduler requested drain; no "
                        "longer accepting tasks", self.id[:8])
            self._draining = True
        return (result.task if result.HasField("task") else None), holdable

    def _stamp_report_wait(self, st, sending: float) -> None:
        """``ledger.report_wait`` on a completion's profile: seconds on
        this clock from the task's end (its window's ``t0`` +
        ``wall_seconds``) to the send of the poll that carries the
        report. Rides the free-form ``TaskProfile.phases`` dict; a
        re-sent report is stamped anew."""
        import json

        from ..observability.ledger import task_phase_key

        try:
            prof = st.completed.profile
            phases = json.loads(prof.phases_json or b"{}")
            phases[task_phase_key("report_wait")] = round(
                max(sending - (prof.t0 + prof.wall_seconds), 0.0), 6)
            prof.phases_json = json.dumps(phases, default=str).encode()
        except Exception:  # noqa: BLE001 - observability only
            log.debug("report_wait not stamped", exc_info=True)

    def _maybe_sample_progress(self):
        """TaskProgress records for this poll, or [] (plane disabled,
        cadence not due, nothing running, or a triggered
        ``scheduler.progress_report`` fault). Samples never force a
        device sync (snapshot_rows resolves only ready scalars) and any
        failure here degrades to an unsampled poll — progress is
        advisory by contract."""
        from ..observability import progress as obs_progress

        interval = obs_progress.progress_interval_secs()
        if interval is None:
            return []
        now = time.time()
        if now - self._last_progress_sample < interval:
            return []
        self._last_progress_sample = now
        with self._progress_lock:
            entries = list(self._running_plans.values())
        if not entries:
            return []
        out = []
        try:
            # chaos surface: "drop" skips this round's piggyback,
            # "delay" stalls it, a "fail" raise is swallowed below —
            # results must be byte-identical under any of them
            if fault_point("scheduler.progress_report",
                           executor=self.id[:8]) == "drop":
                return []
            for entry in entries:
                if entry.get("input_total") is None:
                    # this task executes ONE partition of the shared
                    # stage plan: estimate its per-partition share
                    entry["input_total"] = obs_progress.plan_input_estimate(
                        entry["plan"], per_partition=True)
                s = obs_progress.sample_plan(
                    entry["plan"], input_rows_total=entry["input_total"])
                pid = entry["pid"]
                tp = pb.TaskProgress()
                tp.partition_id.job_id = pid.job_id
                tp.partition_id.stage_id = pid.stage_id
                tp.partition_id.partition_id = pid.partition_id
                tp.stage_version = entry["stage_version"]
                tp.operator = s["operator"] or ""
                tp.rows_so_far = max(int(s["rows_so_far"]), 0)
                tp.input_rows_total = max(int(s["input_rows_total"]), 0)
                tp.bytes_so_far = max(int(s["bytes_so_far"]), 0)
                tp.elapsed_seconds = now - entry["t0"]
                out.append(tp)
        except Exception:  # noqa: BLE001 - best-effort by contract
            log.debug("progress sample failed", exc_info=True)
            return []
        return out

    def _handle_job_cancelled(self, job_id: str):
        """A PollWorkResult carried this job id as cancelled: abort its
        running tasks at their next batch boundary and clean up partial
        stage outputs (completed shuffle files included — nothing will
        ever read them). Idempotent across polls: the id rides every
        poll for a broadcast window."""
        fired = self._fire_tokens(reason="cancelled", job_id=job_id)
        if fired:
            log.info("job %s cancelled; aborting %d running task(s)",
                     job_id, fired)
        # server-side stream abort: chunk streams this executor is
        # serving for the job terminate at their next chunk boundary
        from .dataplane import mark_job_cancelled

        mark_job_cancelled(job_id)
        if job_id not in self._cleaned_jobs:
            self._cleaned_jobs.append(job_id)
            self._cleanup_job_outputs(job_id)

    def _cleanup_job_outputs(self, job_id: str, why: str = "cancelled"):
        """Remove the job's shuffle and result files
        (``dataplane.release`` a job that had files here)."""
        path = os.path.join(self.config.work_dir, job_id)
        if os.path.isdir(path):
            with trace_span("dataplane.release", job=job_id, why=why):
                shutil.rmtree(path, ignore_errors=True)
            log.info("removed %s job outputs: %s", why, path)

    def _remove_released(self, job_ids):
        """Finished jobs whose client has fetched the result."""
        for job_id in job_ids:
            self._cleanup_job_outputs(job_id, "released")

    # -- task execution (in-process; reference: run_received_tasks) ----------

    def _run_task(self, td: pb.TaskDefinition):
        """Start the task on the pool; the caller has claimed its slot,
        and the task's end (or its rejection here) frees it."""
        pid = PartitionId(td.task_id.job_id, td.task_id.stage_id,
                          td.task_id.partition_id)
        # per-task cancel token: registered BEFORE the pool accepts the
        # work so a cancel/drain arriving while the task is still queued
        # aborts it at entry, not after a full execution
        token = CancelToken(job_id=pid.job_id)
        with self._token_lock:
            self._task_tokens[pid.key()] = token
        try:
            plan = serde.physical_from_proto(td.plan)
            # whole-stage fusion happens AFTER deserialization, executor-
            # side: the wire format never carries fused operators, and a
            # re-planned stage's fresh task re-fuses to the same value-
            # keyed signatures (zero new compiles)
            from ..physical.fusion import maybe_fuse

            plan = maybe_fuse(plan)
            shuffle = None
            if td.shuffle_output_partitions:
                hash_exprs = [
                    serde.expr_from_proto(e) for e in td.shuffle_hash_exprs
                ]
                shuffle = (hash_exprs or None, td.shuffle_output_partitions)
        except Exception as e:  # noqa: BLE001 - bad plan/wire payload
            # deserialize/fuse failed BEFORE the pool accepted the work:
            # release the slot and the registered token (a leaked token
            # would make every future drain wait its full bound) and
            # report the failure instead of wedging the task forever
            with self._token_lock:
                self._task_tokens.pop(pid.key(), None)
            self._slots.release()
            log.exception("task %s rejected before execution", pid)
            self.tasks_failed += 1
            self._report_failed(pid, f"{type(e).__name__}: {e}",
                                td.stage_version)
            self._task_ended.set()
            return

        def work():
            from ..observability import distributed as obs_dist
            from ..observability.tracing import flow

            t0 = time.time()
            self._inflight += 1
            # live progress: expose the executing plan to the poll
            # thread's sampler for the duration of the task
            with self._progress_lock:
                self._running_plans[pid.key()] = {
                    "pid": pid, "plan": plan, "t0": t0,
                    "stage_version": td.stage_version,
                    "input_total": None,
                }
            # per-task profile window (distributed profiler): snapshot
            # the process-wide ingest/compile accumulators up front so
            # the completion payload can ship deltas alongside the
            # flight-recorder span window
            capture = obs_dist.task_profile_enabled()
            if capture:
                from ..compile import compile_stats
                from ..ingest import phase_totals

                phases0, compile0 = phase_totals(), compile_stats()
            try:
                # fault point (chaos sweep): an injected failure here is
                # a transient task failure — the scheduler re-queues it
                # within the retry budget
                fault_point("executor.task.start", task=pid.key())
                # token checked at entry (a queued task of an already-
                # cancelled job must not run at all), then bound to the
                # thread so every batch boundary under execute sees it
                token.check()
                # flow(): every span/event emitted while this task runs
                # (ingest producers included — PrefetchHandle re-binds
                # the captured flow on its pool worker) carries the
                # job/stage/task triple for cross-process correlation
                with bind_token(token), \
                        flow(job=pid.job_id, stage=pid.stage_id,
                             task=pid.key()), \
                        trace_span("executor.task", task=pid.key(),
                                   executor=self.id[:8]):
                    if self.mesh_group is not None and _needs_mesh(plan):
                        # group task: broadcast so every member process
                        # enters the SPMD program together; serialized (the
                        # collectives must align across processes)
                        with self.mesh_group.lock:
                            seq = self.mesh_group.broadcast(
                                td.SerializeToString())
                            stats = self.execute_partition(pid, plan, shuffle)
                            self.mesh_group.wait_acks(seq)
                    else:
                        stats = self.execute_partition(pid, plan, shuffle)
                profile = None
                if capture:
                    try:
                        profile = obs_dist.capture_task_profile(
                            pid.key(), t0, time.time() - t0, self.id,
                            phases0=phases0, compile0=compile0)
                    except Exception:  # noqa: BLE001 - observability
                        log.exception("task profile capture failed")
                self._report_completed(pid, stats, td.stage_version,
                                       profile=profile)
                self.tasks_completed += 1
                # same shape as the scheduler's query ring entries
                # (status/wall_seconds/output_rows — the systables
                # record contract), "rows"/"state" kept as legacy keys
                self._query_log.record({
                    "task": pid.key(), "state": "completed",
                    "status": "completed",
                    "wall_seconds": round(time.time() - t0, 4),
                    "rows": int(stats.get("num_rows", 0)),
                    "output_rows": int(stats.get("num_rows", 0)),
                })
            except QueryCancelled as e:
                # cooperative abort at a batch boundary: terminal for
                # this attempt but NOT a failure. The report is still
                # filed ("QueryCancelled:" is transient-shaped): for a
                # job-level cancel the scheduler drops it; for a drain
                # the job is live and the task re-queues elsewhere.
                log.info("task %s cancelled (%s)", pid, e.reason)
                self.tasks_cancelled += 1
                self._query_log.record({
                    "task": pid.key(), "state": "cancelled",
                    "status": "cancelled",
                    "wall_seconds": round(time.time() - t0, 4),
                    "cancel_reason": e.reason,
                })
                self._report_failed(pid, f"{type(e).__name__}: {e}",
                                    td.stage_version)
                # a JOB-level cancel removes the job's outputs (the
                # poll-side cleanup may have run before this task
                # released its write handle). A drain must NOT: the job
                # is live and this executor's earlier completed stage
                # files may still be fetched while the drain grace runs
                if e.reason != "drain":
                    self._cleanup_job_outputs(pid.job_id)
            except Exception as e:  # noqa: BLE001 - task failure
                log.exception("task %s failed", pid)
                self.tasks_failed += 1
                self._query_log.record({
                    "task": pid.key(), "state": "failed",
                    "status": "failed",
                    "wall_seconds": round(time.time() - t0, 4),
                    "error": f"{type(e).__name__}: {e}"[:300],
                })
                # prefix the exception class: the scheduler retries
                # transient (IO-shaped) failures but fails fast on
                # deterministic ones (bad plans, overflow limits)
                self._report_failed(pid, f"{type(e).__name__}: {e}",
                                    td.stage_version)
            finally:
                with self._token_lock:
                    self._task_tokens.pop(pid.key(), None)
                with self._progress_lock:
                    self._running_plans.pop(pid.key(), None)
                self._inflight -= 1
                self._slots.release()
                # AFTER the slot is free: the poll this wakes offers it,
                # so the reply can bring the next stage's task
                self._task_ended.set()

        self._pool.submit(work)

    def execute_partition(self, pid: PartitionId, plan,
                          shuffle=None) -> dict:
        """Run one stage partition and STREAM its output to disk
        (reference: flight_service.rs:89-192). Batches are written as
        they are produced — bounded Arrow-IPC chunks through
        ``ipc.PartitionWriter`` — so the executor never holds a whole
        partition's output alongside its conversion buffers; the cancel
        token is checked at every batch pull AND every chunk write.
        With ``shuffle`` ((hash_exprs|None, n_out)) the output is
        hash/round-robin split into one shuffle-q file per consumer
        partition."""
        from ..io import ipc
        from ..ingest import cancel_plan, prime_plan

        t0 = time.time()
        # parallel ingest: start this task's leaf-scan parse+H2D on the
        # pool before pulling, so a plan with several scan leaves (e.g.
        # a merged join stage) parses them concurrently; primed handles
        # an aborted task leaves behind are cancelled, never leaked
        prime_plan(plan, partitions=[pid.partition_id])
        if shuffle is not None:
            try:
                stats = self._write_shuffled(pid, plan, shuffle, t0)
            finally:
                # handles the plan never consumed (failures) must not
                # leave producers parked on full queues
                cancel_plan(plan)
            stats["task_metrics"] = self._harvest_metrics(
                plan, time.time() - t0, stats, shuffled=True)
            return stats
        path = partition_path(self.config.work_dir, pid.job_id, pid.stage_id,
                              pid.partition_id)
        writer = ipc.PartitionWriter(path, schema=plan.output_schema(),
                                     compute_column_stats=True)
        try:
            with trace_span("dataplane.write", path=path):
                for batch in plan.execute(pid.partition_id):
                    # cooperative cancellation at the batch boundary: a
                    # fired token (job cancel, drain) stops the pull
                    # here; cancel_plan below unparks ingest producers
                    check_cancel()
                    writer.write_batch(batch)
                # empty partition: close() synthesizes one empty batch
                # with the plan schema
                stats = writer.close()
        except BaseException:
            writer.abort()
            raise
        finally:
            cancel_plan(plan)
        log.info("executed %s in %.1fs (%d rows)", pid.key(),
                 time.time() - t0, stats["num_rows"])
        out = {**stats, "path": path}
        out["task_metrics"] = self._harvest_metrics(
            plan, time.time() - t0, stats, write_secs=writer.write_seconds)
        return out

    def _harvest_metrics(self, plan, elapsed_total: float, stats: dict,
                         shuffled: bool = False,
                         write_secs: float = 0.0) -> "dict | None":
        """Per-operator metrics off the executed plan + a synthetic
        write-side row (shuffle/partition IPC write happens outside the
        plan, so bytes_written needs its own operator row; its position
        is stable across tasks of a stage, keeping positional stage
        aggregation valid)."""
        if not metrics_enabled():
            return None
        ops = collect_plan_metrics(plan)
        write_row = {
            "operator": "ShuffleWrite" if shuffled else "PartitionWrite",
            "depth": 0,
            "metrics": {"bytes_written": int(stats.get("num_bytes", 0)),
                        # the shuffle.write event's numbers: counters, so
                        # a stage's row sums them over its tasks
                        **stats.get("shuffle_write", {})},
        }
        if write_secs:
            write_row["metrics"]["elapsed_write"] = write_secs
        ops.append(write_row)
        return {"operators": ops, "elapsed_total": elapsed_total}

    def _write_shuffled(self, pid: PartitionId, plan, shuffle,
                        t0: float) -> dict:
        """Streaming n_out-way shuffle write: every produced batch is
        partitioned ONCE and its slices appended to the
        per-consumer-partition stream writers IMMEDIATELY, so neither
        the stage output nor its Arrow conversion buffers ever
        accumulate. A batch costs one governed program
        (``jit_shuffle_dest``: every row's destination, dead rows last),
        one blocking read of that vector and one of each column, and one
        stable permutation on the host shared by all columns
        (``ipc.partition_to_arrow``); a destination costs a zero-copy
        slice and its file write, nothing on the device. Host memory
        peaks at one column's full-capacity copy plus ONE batch's
        gathered columns (five int64 columns of a 1,048,576-row batch:
        40 MB a task) beside one bounded chunk per writer.
        Record-batch structure matches the old materialize-then-write
        path (one batch per (input batch, q), empty ones included, plus
        chunk splits, rows in the batch's order), keeping results
        identical."""
        import numpy as np

        from ..columnar import empty_batch
        from ..io import ipc
        from ..physical.operators import shuffle_dest_program
        from .dataplane import shuffle_path

        hash_exprs, n_out = shuffle
        schema = plan.output_schema()
        dest_of = shuffle_dest_program(schema, hash_exprs, n_out)
        writers = []
        base = None
        for q in range(n_out):
            path = shuffle_path(self.config.work_dir, pid.job_id,
                                pid.stage_id, pid.partition_id, q)
            base = path
            writers.append(ipc.PartitionWriter(path, schema=schema))
        totals = {"num_rows": 0, "num_batches": 0, "num_bytes": 0}
        offset = 0
        produced = 0
        try:
            with trace_span("dataplane.write", task=pid.key(),
                            fan_out=n_out):
                for b in plan.execute(pid.partition_id):
                    check_cancel()
                    # round-robin reads the offset modulo n_out only
                    dest = dest_of(b, np.int32(n_out),
                                   np.int32(offset % n_out))
                    for w, rb in zip(writers, ipc.partition_to_arrow(
                            b, dest, n_out)):
                        # a destination, on top of write_arrow's check a
                        # chunk (w is dynamic: the analyzer cannot
                        # follow the call)
                        check_cancel()
                        w.write_arrow(rb)
                        offset += rb.num_rows
                    produced += 1
                if not produced:
                    # a task that yielded nothing: every file carries
                    # the one empty schema-bearing batch, converted once
                    rb = ipc.batch_to_arrow(empty_batch(schema))
                    for w in writers:
                        w.write_arrow(rb)
                # per-output-partition byte histogram: the signal
                # adaptive re-planning coalesces/splits the consuming
                # stage on
                qbytes = []
                for w in writers:
                    st = w.close()
                    qbytes.append(int(st["num_bytes"]))
                    for k in totals:
                        totals[k] += st[k]
        except BaseException:
            for w in writers:
                w.abort()
            raise
        totals["shuffle_partition_bytes"] = qbytes
        # slices = record batches written before chunking (batches x
        # fan-out); reads = the blocking device-to-host reads the write
        # made: 1 + columns a batch (partition_to_arrow; batch_to_arrow
        # for the one empty batch), whatever the fan-out
        slices = produced * n_out
        reads = max(produced, 1) * (1 + len(schema))
        wrote = {"shuffle_fan_out": n_out, "shuffle_batches": produced,
                 "shuffle_slices": slices, "shuffle_reads": reads}
        trace_event("shuffle.write", task=pid.key(), fan_out=n_out,
                    batches=produced, slices=slices, reads=reads,
                    rows=totals["num_rows"], bytes=totals["num_bytes"])
        log.info("executed %s (shuffle x%d) in %.1fs (%d rows)", pid.key(),
                 n_out, time.time() - t0, totals["num_rows"])
        return {**totals, "path": base, "shuffle_write": wrote}

    def _report_completed(self, pid: PartitionId, stats: dict,
                          stage_version: int = 0, profile=None):
        ts = pb.TaskStatus()
        ts.partition_id.job_id = pid.job_id
        ts.partition_id.stage_id = pid.stage_id
        ts.partition_id.partition_id = pid.partition_id
        ts.stage_version = stage_version
        ts.completed.executor_id = self.id
        ts.completed.path = stats["path"]
        tm = stats.get("task_metrics")
        if tm:
            serde.task_metrics_to_proto(tm, ts.completed.metrics)
        if profile:
            serde.task_profile_to_proto(profile, ts.completed.profile)
        serde.stats_to_proto(stats, ts.completed.stats)
        with self._status_lock:
            self._pending_status.append(ts)

    def _report_failed(self, pid: PartitionId, error: str,
                       stage_version: int = 0):
        ts = pb.TaskStatus()
        ts.partition_id.job_id = pid.job_id
        ts.partition_id.stage_id = pid.stage_id
        ts.partition_id.partition_id = pid.partition_id
        ts.stage_version = stage_version
        ts.failed.error = error
        with self._status_lock:
            self._pending_status.append(ts)


# ---------------------------------------------------------------------------
# Local cluster helper (reference: executor --local mode, main.rs:101-138)
# ---------------------------------------------------------------------------


class LocalCluster:
    """In-process scheduler + N executors (for tests and single-host use)."""

    def __init__(self, num_executors: int = 2, concurrent_tasks: int = 2,
                 scheduler_port: int = 0, num_devices: int = 1,
                 speculation_age_secs: float = 60.0,
                 metrics_port: "int | None" = None,
                 backend=None):
        from .scheduler import serve_scheduler
        from .state import MemoryBackend, SchedulerState

        # metrics_port: None = off (in-process test clusters shouldn't
        # bind sockets unasked); 0 = ephemeral health plane on the
        # scheduler AND every executor
        # backend: a durable KvBackend (e.g. SqliteBackend) makes this
        # in-process cluster restart-recoverable — the controlplane
        # tests rebuild a LocalCluster over the same file
        self.state = SchedulerState(backend or MemoryBackend())
        self.server, self.service, self.port = serve_scheduler(
            self.state, "localhost", scheduler_port,
            speculation_age_secs=speculation_age_secs,
            metrics_port=metrics_port,
        )
        # remember the executor shape: the autoscaler's add_executor
        # hook spawns clones of the launch-time fleet
        self._exec_kwargs = dict(
            concurrent_tasks=concurrent_tasks,
            num_devices=num_devices,
            # executors always take an ephemeral port (several per
            # host; a fixed one could only serve the first); a
            # negative caller value means OFF here too (-1, not
            # None — None would fall back to the env default and
            # re-enable what the caller explicitly disabled)
            metrics_port=(None if metrics_port is None
                          else 0 if metrics_port >= 0 else -1),
        )
        self.executors = []
        for _ in range(num_executors):
            self.add_executor()

    def add_executor(self) -> "Executor":
        """Spawn one more in-process executor (the autoscaler's
        LocalCluster scale-up hook)."""
        cfg = ExecutorConfig(
            scheduler_host="localhost", scheduler_port=self.port,
            **self._exec_kwargs,
        )
        e = Executor(cfg)
        e.start()
        self.executors.append(e)
        return e

    def remove_executor(self, executor_id: "str | None" = None
                        ) -> "str | None":
        """Gracefully drain one executor (the autoscaler's LocalCluster
        scale-down hook): the youngest, or the one with ``executor_id``.
        Returns the drained executor's id, or None when empty."""
        if not self.executors:
            return None
        if executor_id is None:
            e = self.executors.pop()
        else:
            match = [x for x in self.executors if x.id == executor_id]
            if not match:
                return None
            e = match[0]
            self.executors.remove(e)
        e.stop(drain=True)
        return e.id

    @property
    def scheduler_health_port(self) -> "int | None":
        h = getattr(self.service, "health", None)
        return h.port if h is not None else None

    def shutdown(self):
        for e in self.executors:
            e.stop()
        self.service.close_health()
        self.server.stop(grace=None)
