"""Client API: BallistaContext + DataFrame.

Mirrors the reference's client crate surface (reference:
rust/client/src/context.rs:75-144 ``BallistaContext`` with remote/
read_csv/read_parquet/register_*/sql; :149-315 ``BallistaDataFrame`` verbs
select/filter/aggregate/sort/limit/repartition/collect) and its Python
bindings (reference: python/src/context.rs, python/src/dataframe.rs).

Two modes:
- ``standalone()``: plans and executes in-process (single host, one device);
- ``remote(host, port)``: submits plans to a scheduler over gRPC and fetches
  results from executors (distributed layer).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple, Union

from .datatypes import Schema, dtype_from_name, schema as make_schema
from .errors import BallistaError, PlanError
from . import expr as ex
from .io import CsvSource, MemTableSource, ParquetSource, TblSource
from .logical import (
    Aggregate,
    Filter,
    Join,
    Limit,
    LogicalPlan,
    LogicalPlanBuilder,
    Projection,
    Repartition,
    Sort,
    TableScan,
    TableSource,
)
from .sql.parser import CreateExternalTable, Query, parse_sql
from .sql.planner import CatalogTable, SqlPlanner


def _default_pk(schema: Schema) -> Optional[str]:
    """TPC-H-style convention: a first column named *key is the primary key."""
    names = schema.names()
    if names and names[0].endswith("key"):
        return names[0]
    return None


class BallistaContext:
    """Entry point: table registration + SQL/DataFrame construction."""

    def __init__(self, mode: str = "standalone", host: str = "localhost",
                 port: int = 50050, settings: Optional[Dict[str, str]] = None):
        self.mode = mode
        self.host = host
        self.port = port
        self.settings = dict(settings or {})
        # per-session resource metering (observability/progress.py):
        # every query this context runs is accounted to one session id.
        # It travels with the submitted settings so the scheduler's
        # terminal hook can meter cluster jobs; a caller-supplied
        # "session.id" setting wins (shared-session pools).
        import uuid

        self.session_id = self.settings.setdefault(
            "session.id", uuid.uuid4().hex[:12])
        self._catalog: Dict[str, CatalogTable] = {}
        # SQL plan cache: repeated identical queries reuse the planned (and,
        # in standalone mode, compiled) DataFrame; invalidated on any
        # catalog change
        self._plan_cache: Dict[str, "DataFrame"] = {}
        # per-stage operator metrics of the last executed query
        # (observability subsystem); None until a query completes or when
        # metrics are disabled (BALLISTA_METRICS=0). Standalone queries
        # stash the executed plan and snapshot LAZILY at read time —
        # harvesting inside collect() would put a device_get + plan walk
        # on every query's critical path (the < 5% overhead gate)
        self._last_query_metrics = None
        self._last_query_phys = None
        # job id of the last remote query: the handle df.profile() and
        # /debug/profile/<job_id> take on the cluster path
        self._last_job_id = None
        # latency ledger (observability/ledger.py): standalone collects
        # stash their assembled ledger; remote collects stash only the
        # client envelope (wall + client-side stamps) and
        # last_query_ledger() merges it with the scheduler's
        # system.latency rows LAZILY — no RPC on the collect path
        self._last_query_ledger = None
        self._last_ledger_env = None
        # query lifecycle (lifecycle.py / docs/robustness.md): cancel
        # tokens of in-flight standalone collects and the live job-id
        # sinks of in-flight remote collects — what ctx.cancel() fires
        # from another thread
        self._lifecycle_lock = threading.Lock()
        self._active_tokens: List = []
        self._active_job_sinks: List[list] = []

    # -- constructors -------------------------------------------------------

    @staticmethod
    def standalone(**settings) -> "BallistaContext":
        return BallistaContext("standalone", settings=settings or None)

    @staticmethod
    def remote(host: str, port: int = 50050, **settings) -> "BallistaContext":
        return BallistaContext("remote", host, port, settings or None)

    # -- registration (reference: context.rs:110-129) -----------------------

    def register_source(self, name: str, source: TableSource,
                        primary_key: Optional[str] = None,
                        cached: bool = False) -> None:
        if cached:
            from .io import CacheSource

            source = CacheSource(source)
        pk = primary_key or _default_pk(source.table_schema())
        self._catalog[name] = CatalogTable(name, source, pk)
        self._plan_cache.clear()

    def register_tbl(self, name: str, path: str, schema: Schema,
                     primary_key: Optional[str] = None, cached: bool = False,
                     **kw) -> None:
        self.register_source(name, TblSource(path, schema, **kw), primary_key,
                             cached=cached)

    def register_csv(self, name: str, path: str, schema: Schema,
                     has_header: bool = True,
                     primary_key: Optional[str] = None, cached: bool = False,
                     **kw) -> None:
        self.register_source(
            name, CsvSource(path, schema, has_header=has_header, **kw),
            primary_key, cached=cached,
        )

    def register_parquet(self, name: str, path: str,
                         schema: Optional[Schema] = None,
                         primary_key: Optional[str] = None,
                         cached: bool = False, **kw) -> None:
        self.register_source(name, ParquetSource(path, schema, **kw),
                             primary_key, cached=cached)

    def register_memtable(self, name: str, schema: Schema, data: Dict,
                          num_partitions: int = 1,
                          primary_key: Optional[str] = None) -> None:
        self.register_source(
            name, MemTableSource.from_pydict(schema, data, num_partitions),
            primary_key,
        )

    def register_table(self, name: str, df: "DataFrame") -> None:
        """Register a DataFrame as a named table (view semantics): SQL
        referencing ``name`` inlines the frame's logical plan, exactly
        the role the reference's DFTableAdapter plays for registered
        frames (reference: rust/core/src/datasource.rs:28-66;
        rust/client/src/context.rs:131-144 registers DataFrames before
        planning SQL)."""
        # df.plan plans raw-SQL (server-planned) frames on demand and
        # raises PlanError for true DDL frames that carry no plan.
        # Copy it: executing the original frame mutates its plan in
        # place (scalar subqueries resolve to literals) and the view
        # must not inherit those baked values. Sources are shared
        # (TableSource.__deepcopy__).
        import copy

        self._catalog[name] = CatalogTable(name, None, None,
                                           plan=copy.deepcopy(df.plan))
        self._plan_cache.clear()

    def deregister_table(self, name: str) -> None:
        self._catalog.pop(name, None)
        self._plan_cache.clear()

    def tables(self) -> List[str]:
        return sorted(self._catalog)

    # -- reads (reference: context.rs:88-108) -------------------------------

    def read_tbl(self, path: str, schema: Schema, **kw) -> "DataFrame":
        src = TblSource(path, schema, **kw)
        return DataFrame(self, TableScan("tbl:" + path, src))

    def read_csv(self, path: str, schema: Schema, has_header: bool = True,
                 **kw) -> "DataFrame":
        src = CsvSource(path, schema, has_header=has_header, **kw)
        return DataFrame(self, TableScan("csv:" + path, src))

    def read_parquet(self, path: str, schema: Optional[Schema] = None,
                     **kw) -> "DataFrame":
        src = ParquetSource(path, schema, **kw)
        return DataFrame(self, TableScan("parquet:" + path, src))

    def _system_source(self, name: str):
        """Scan source for a ``system.*`` table: the current process's
        snapshot in standalone mode; in remote mode rows are fetched
        from the SCHEDULER at scan/ship time so they reflect cluster
        state (observability/systables.py)."""
        from .observability.systables import SystemTableSource

        if self.mode == "remote":
            host, port = self.host, self.port

            def fetch():
                from .distributed.client import fetch_system_table

                return fetch_system_table(host, port, name)

            return SystemTableSource(name, fetcher=fetch)
        return SystemTableSource(name)

    def table(self, name: str) -> "DataFrame":
        if name not in self._catalog:
            from .observability.systables import is_system_table

            if is_system_table(name):
                return DataFrame(
                    self, TableScan(name, self._system_source(name)))
            raise PlanError(f"unknown table {name!r}")
        t = self._catalog[name]
        if t.plan is not None:  # registered DataFrame view: inline a
            # copy — execution mutates plans in place and the catalog's
            # must stay pristine (sources are shared, not cloned)
            import copy

            return DataFrame(self, copy.deepcopy(t.plan))
        return DataFrame(self, TableScan(t.name, t.source))

    # -- SQL ----------------------------------------------------------------

    def sql(self, query: str) -> "DataFrame":
        cached = self._plan_cache.get(query)
        if cached is not None:
            return cached
        if (self.mode == "remote"
                and self.settings.get("plan.server") in ("on", "true", "1")
                and not _is_ddl(query)):
            # raw-SQL submission: the scheduler plans against the catalog
            # shipped with the query (no client-side planning at collect
            # time; DDL still registers in the client catalog below)
            return DataFrame(self, None, raw_sql=query)
        stmt = parse_sql(query)
        if isinstance(stmt, CreateExternalTable):
            sch = make_schema(*[(n, t) for n, t in stmt.columns])
            if stmt.stored_as in ("CSV",):
                self.register_csv(stmt.name, stmt.location, sch,
                                  has_header=stmt.has_header)
            elif stmt.stored_as in ("TBL",):
                self.register_tbl(stmt.name, stmt.location, sch)
            elif stmt.stored_as in ("PARQUET",):
                self.register_parquet(stmt.name, stmt.location, sch)
            else:
                raise PlanError(f"STORED AS {stmt.stored_as} unsupported")
            return DataFrame(self, None)
        planner = SqlPlanner(self._catalog,
                             system_provider=self._system_source)
        df = DataFrame(self, planner.plan(stmt))
        # plans over system.* tables are NOT cached: a cached plan reuses
        # its physical operator instances, whose materializations (a
        # JoinExec build side, RepartitionExec parts) would freeze the
        # telemetry snapshot of the FIRST collect — re-issuing the SQL
        # must see fresh rows (observability/systables.py)
        if not _scans_system_table(df._plan):
            self._plan_cache[query] = df
        return df

    # -- execution ----------------------------------------------------------

    @contextmanager
    def _track_lifecycle(self, obj, registry: list):
        """Register an in-flight query's cancel handle (a CancelToken
        or a live remote job-id sink) for the duration of the collect,
        so a concurrent ``ctx.cancel()`` can reach it."""
        with self._lifecycle_lock:
            registry.append(obj)
        try:
            yield obj
        finally:
            with self._lifecycle_lock:
                try:
                    registry.remove(obj)
                except ValueError:
                    pass

    def cancel(self, reason: str = "client") -> int:
        """Cooperatively cancel this context's in-flight queries (call
        from another thread). Standalone collects stop at their next
        batch boundary and raise :class:`errors.QueryCancelled`; remote
        collects get a best-effort ``CancelJob`` for every job this
        context currently has in flight. Returns how many queries/jobs
        this call cancelled. Queries land as terminal ``cancelled`` in
        ``system.queries`` with the given reason."""
        with self._lifecycle_lock:
            tokens = list(self._active_tokens)
            sinks = list(self._active_job_sinks)
            job_ids = [jid for sink in sinks
                       for jid in list(sink) if isinstance(jid, str)]
        n = 0
        for t in tokens:
            n += bool(t.cancel(reason))
        if self.mode == "remote" and sinks:
            import logging

            from .distributed.client import CancelRequested, cancel_job

            for jid in job_ids:
                try:
                    n += bool(cancel_job(self.host, self.port, jid,
                                         reason))
                except Exception:  # noqa: BLE001 - best-effort
                    logging.getLogger("ballista.lifecycle").warning(
                        "CancelJob(%s) failed", jid, exc_info=True)
            # a collect sleeping between admission-retry attempts has
            # no live job to CancelJob: the sentinel stops its loop
            # before it resubmits the query the user just cancelled
            for sink in sinks:
                sink.append(CancelRequested(reason))
        return n

    def _collect(self, plan: LogicalPlan, on_progress=None):
        if self.mode == "standalone":
            out, _ = self._standalone_collect(plan,
                                              on_progress=on_progress)
            return out
        import time as _time

        from .distributed.client import remote_collect
        from .observability import ledger as _ledger

        sink: list = []
        jsink: list = []
        _ledger.begin_collect()
        t0 = _time.perf_counter()
        # jsink receives the job id at SUBMIT time, so a concurrent
        # ctx.cancel() can CancelJob the job while this thread waits
        with self._track_lifecycle(jsink, self._active_job_sinks):
            out = remote_collect(self.host, self.port, plan, self.settings,
                                 metrics_out=sink, job_id_out=jsink,
                                 on_progress=on_progress)
        self._last_query_metrics = sink[0] if sink else None
        self._last_query_phys = None
        self._last_job_id = jsink[0] if jsink else None
        self._last_query_ledger = None
        self._last_ledger_env = {"wall": _time.perf_counter() - t0,
                                 "stamps": _ledger.take_collect()}
        return out

    def job_progress(self, job_id: Optional[str] = None):
        """Live progress snapshot of a job (the ONE progress shape —
        see docs/observability.md): per-stage completion fractions,
        rate-based ETA, task counts. ``job_id`` defaults to this
        context's most recent remote job. Remote contexts ask the
        scheduler (extended GetJobStatus); standalone contexts report
        their in-flight collects. Returns None when nothing is known
        about the job."""
        if self.mode == "remote":
            jid = job_id
            if not jid:
                # prefer a currently in-flight job (another thread's
                # collect registered its id at SUBMIT time — the same
                # channel ctx.cancel() uses) over the last finished one
                with self._lifecycle_lock:
                    inflight = [j for sink in self._active_job_sinks
                                for j in list(sink)
                                if isinstance(j, str)]
                jid = (inflight[-1] if inflight else None) \
                    or self._last_job_id
            if not jid:
                return None
            from .distributed.client import fetch_job_progress

            return fetch_job_progress(self.host, self.port, jid)
        from .observability import progress as obs_progress

        handles = obs_progress.local_live_handles()
        if job_id is not None:
            handles = [h for h in handles if h.job_id == job_id]
        return handles[-1].snapshot() if handles else None

    def _standalone_collect(self, plan: LogicalPlan, phys=None,
                            on_progress=None):
        """Shared standalone execute-and-wrap: plan (unless the caller
        passes a cached physical plan), execute, record metrics.
        Returns ``(frame, phys)`` so DataFrame.collect can keep its
        plan cache. Under ``BALLISTA_PROFILE=<dir>`` every collect
        writes a Chrome-trace profile artifact into the directory.
        Every collect's terminal summary (status, wall seconds, output
        rows, flight-recorder lanes, artifact path) lands in the shared
        system-tables snapshot + the durable query-history log
        (observability/systables.py) — the standalone face of the
        scheduler's terminal-transition hook. ``on_progress`` (live
        progress plane) receives snapshots of the ONE progress shape
        from a sampler thread over the executing plan's MetricsSet —
        parity with the cluster path's GetJobStatus-driven callbacks."""
        from .observability.systables import StandaloneQueryRecorder

        rec = StandaloneQueryRecorder(plan, session_id=self.session_id)
        sampler = None
        if on_progress is not None:
            from .observability.progress import LocalProgressSampler

            sampler = LocalProgressSampler(rec.handle, on_progress)
        try:
            out, phys2 = self._standalone_collect_routed(plan, phys, rec)
        except Exception as e:  # noqa: BLE001 - record, then propagate
            from .errors import QueryCancelled

            if sampler is not None:
                sampler.finish("cancelled" if isinstance(e, QueryCancelled)
                               else "failed")
            rec.finish("failed", error=e)
            self._last_query_ledger = rec.ledger
            self._last_ledger_env = None
            raise
        if sampler is not None:
            # terminal callback BEFORE the recorder tears the handle
            # down: the final snapshot reports fraction exactly 1.0
            sampler.finish("completed")
        rec.finish("completed", result=out, phys=phys2)
        self._last_query_ledger = rec.ledger
        self._last_ledger_env = None
        return out, phys2

    def _standalone_collect_routed(self, plan: LogicalPlan, phys, rec):
        from .observability import profiler as obs_profiler

        out_dir = obs_profiler.profile_dir()
        if out_dir is not None and not obs_profiler.profiling_active():
            # label artifacts by a plan digest so a bench loop's files
            # are distinguishable per query shape
            try:
                profile_label = "query-" + obs_profiler.plan_digest(plan)
            except Exception:  # noqa: BLE001 - label is cosmetic
                profile_label = "query"
            box = {}

            def run():
                box["r"] = self._standalone_collect_inner(plan, phys)

            import logging

            plog = logging.getLogger("ballista.profiler")
            try:
                _, path = obs_profiler.profile_call(
                    run, label=profile_label,
                    plan_getter=lambda: box.get("r", (None, None))[1],
                    out_dir=out_dir, busy_ok=True,
                )
            except Exception:
                if "r" not in box:
                    raise  # the QUERY failed: propagate as usual
                # the query succeeded and only the artifact write/stop
                # failed (e.g. unwritable BALLISTA_PROFILE path): a
                # misconfigured observability knob must not cost the
                # caller their result
                plog.exception("profile artifact write failed; "
                               "returning the query result anyway")
                path = None
            if path is not None:
                plog.info("profile artifact written: %s", path)
                rec.artifact_path = path
            return box["r"]
        # unprofiled run: the always-on flight recorder still lets a
        # query that crosses BALLISTA_SLOW_QUERY_SECS dump a RETROACTIVE
        # merged artifact after the fact (no-op when the knob is unset)
        from .observability.distributed import watch_slow_query

        def slow_label():
            return "query-" + obs_profiler.plan_digest(plan)

        slow_sink: list = []
        try:
            with watch_slow_query(slow_label, artifact_out=slow_sink):
                return self._standalone_collect_inner(plan, phys)
        finally:
            if slow_sink:
                rec.artifact_path = slow_sink[0]

    def _standalone_collect_inner(self, plan: LogicalPlan, phys=None):
        from .lifecycle import CancelToken, bind_token, slow_query_killer

        # one cancel token per collect: ctx.cancel() fires it from
        # another thread, the slow-query killer fires it on timeout,
        # and every batch boundary under the bind checks it
        token = CancelToken()
        with self._track_lifecycle(token, self._active_tokens), \
                bind_token(token), slow_query_killer(token):
            return self._standalone_collect_governed(plan, phys)

    def _standalone_collect_governed(self, plan: LogicalPlan, phys=None):
        import pandas as pd

        from .execution import collect_physical, plan_logical
        from .observability.metrics import (metrics_enabled,
                                            reset_plan_metrics)
        from .observability.ledger import ledger_phase
        from .physical.planner import PlannerOptions

        with ledger_phase("planning"):
            if phys is None:
                phys = plan_logical(
                    plan, PlannerOptions.from_settings(self.settings))
            # whole-stage fusion (physical/fusion.py): merge each
            # pipeline stage into one governed XLA program. Before
            # the adaptive pass (fused stages survive re-planning via
            # with_new_children).
            from .physical.fusion import maybe_fuse

            phys = maybe_fuse(phys)
        # plan-fingerprint result cache (cache/results.py, opt-in): a
        # repeat of the same fused plan over unchanged files with the
        # same settings returns the stored pydict without executing.
        # Keyed AFTER fusion so the fingerprint covers the real
        # programs; EXPLAIN trees execute nothing worth caching and
        # ANALYZE must re-measure, so both bypass.
        from .cache import results as _results
        from .physical.explain import ExplainAnalyzeExec, ExplainExec

        rc_key = None
        if (_results.result_cache_enabled(self.settings)
                and not isinstance(phys, (ExplainAnalyzeExec, ExplainExec))):
            rc_key = _results.plan_key(phys, self.settings)
            cached = _results.process_result_cache().lookup(rc_key)
            if cached is not None:
                self._annotate_cache_hits(result_hit=True)
                with ledger_phase("host_decode"):
                    out = pd.DataFrame(cached)
                return out, phys
        if metrics_enabled():
            # cached plans re-execute: last_query_metrics() must report
            # THIS query, not the lifetime accumulation — and the reset
            # drains pending device row-count scalars, which would
            # otherwise grow unboundedly when metrics are never read
            reset_plan_metrics(phys)
        # Parallel ingest (ballista_tpu/ingest): start parse+H2D for
        # every leaf scan NOW, so independent tables overlap each other
        # and the adaptive pass's eager repartition materialization
        # below consumes already-running streams. Scan INSTANCES
        # survive the adaptive rewrite (with_new_children keeps
        # leaves), so primed handles are consumed by the re-planned
        # tree; anything a rewrite or early exit leaves behind is
        # cancelled, never leaked.
        from .ingest import cancel_plan, prime_plan

        prime_plan(phys)
        try:
            phys = self._apply_adaptive(phys)
            # live progress plane: expose the FINAL (post-adaptive)
            # tree to this thread's active query handle — the
            # on_progress sampler and system.tasks/system.stages read
            # it weakly (no-op for unrecorded inner collects: EXPLAIN,
            # df.profile()). After the adaptive pass so the weak ref
            # survives: a rewritten root replaces the planned one.
            from .observability import progress as obs_progress

            obs_progress.attach_current_plan(phys)
            data = collect_physical(phys)
            with ledger_phase("host_decode"):
                out = pd.DataFrame(data)
        finally:
            cancel_plan(phys)
        self._record_plan_metrics(phys)
        if rc_key is not None:
            _results.process_result_cache().fill(rc_key, data)
        self._annotate_cache_hits(phys)
        return out, phys

    def _annotate_cache_hits(self, phys=None, result_hit=False) -> None:
        """Per-session warm-path attribution (system.sessions): sum the
        plan's ScanExec table_cache_hits counters for THIS collect
        (reset_plan_metrics zeroed them at entry) and/or flag a
        result-cache hit. Never bumps the meter's query count."""
        from .observability.progress import process_session_meter

        hits = 0
        if phys is not None:
            def walk(node):
                nonlocal hits
                m = getattr(node, "_metrics", None)
                if m is not None:
                    hits += int(m._counters.get("table_cache_hits", 0) or 0)
                for c in node.children():
                    walk(c)

            walk(phys)
        if hits or result_hit:
            process_session_meter().annotate_cache(
                self.settings.get("session.id"), hits,
                1 if result_hit else 0)

    def _apply_adaptive(self, phys):
        """Standalone adaptive execution: rewrite the planned tree from
        observed pipeline-breaker histograms (adaptive/standalone.py).
        Runs once per plan — cached DataFrames keep the adapted tree —
        and leaves EXPLAIN [ANALYZE] leaves alone (ANALYZE applies the
        rules itself, inside its measured window)."""
        if getattr(phys, "_adaptive_applied", False):
            return phys
        from .adaptive import AdaptiveConfig
        from .adaptive.standalone import apply_adaptive_rules
        from .physical.explain import ExplainAnalyzeExec, ExplainExec

        if not isinstance(phys, (ExplainAnalyzeExec, ExplainExec)):
            conf = AdaptiveConfig.from_settings(self.settings)
            if conf.enabled:
                phys = apply_adaptive_rules(phys, conf)
                # re-fuse subtrees the rewrite restructured (e.g. a
                # demoted join's probe chain). Value-keyed signatures
                # mean re-fused stages hit the existing governed
                # entries — zero new compiles. Probe-chain fusion is
                # skipped: a demoted join must keep the compiled probe
                # programs it already has.
                from .physical.fusion import fuse_plan, fusion_enabled

                if fusion_enabled():
                    phys = fuse_plan(phys, fuse_joins=False)
                    try:
                        # the re-fused root is cached: without the
                        # marker the NEXT collect would re-run the full
                        # pass (fuse_joins=True) and fuse the demoted
                        # join's probe chain after all
                        phys._fusion_applied = True
                    except AttributeError:
                        pass
        phys._adaptive_applied = True
        return phys

    def _record_plan_metrics(self, phys) -> None:
        from .observability.metrics import metrics_enabled

        self._last_query_metrics = None
        self._last_query_phys = phys if metrics_enabled() else None

    def last_query_metrics(self):
        """Per-stage/operator metric breakdown of the most recent query
        this context executed (:class:`observability.QueryMetrics`), or
        None before any query / under ``BALLISTA_METRICS=0``. Standalone
        queries report a single synthetic stage 0; distributed queries
        report the scheduler's per-stage aggregation over completed
        tasks."""
        if self._last_query_metrics is None and \
                self._last_query_phys is not None:
            from .observability.metrics import snapshot_plan_metrics

            self._last_query_metrics = snapshot_plan_metrics(
                self._last_query_phys)
        return self._last_query_metrics

    def last_query_ledger(self):
        """The per-query latency ledger of the most recent query this
        context ran (docs/observability.md): the fixed phase schema
        (``observability.ledger.LEDGER_PHASES``) plus wall seconds and
        the unattributed remainder, or None before any query / under
        ``BALLISTA_LEDGER=0``. Standalone queries stash the assembled
        ledger at terminal time; remote queries fetch the scheduler's
        ``system.latency`` rows for the job LAZILY here and merge them
        with the client envelope (end-to-end wall, result transfer,
        host decode) — nothing on the collect hot path."""
        if self._last_query_ledger is None and self.mode == "remote" \
                and self._last_job_id and self._last_ledger_env:
            self._last_query_ledger = self._fetch_remote_ledger()
        return self._last_query_ledger

    def _fetch_remote_ledger(self):
        import time as _time

        from .observability import ledger as _ledger

        env = self._last_ledger_env
        job_id = self._last_job_id
        # completion is published before the scheduler's terminal hook
        # records the job ledger (results never wait on observability)
        # — briefly retry until the job's rows appear
        rows = []
        deadline = _time.time() + 5.0
        while True:
            try:
                from .distributed.client import fetch_system_table

                rows = [r for r in fetch_system_table(
                            self.host, self.port, "system.latency")
                        if r.get("job_id") == job_id]
            except Exception:  # noqa: BLE001 - ledger is advisory
                rows = []
            if rows or _time.time() > deadline:
                break
            _time.sleep(0.1)
        phases = {}
        status = "completed"
        for r in rows:
            phase = r.get("phase")
            if phase and phase != "unattributed":
                try:
                    phases[phase] = float(r.get("seconds") or 0.0)
                except (TypeError, ValueError):
                    continue
            status = r.get("status") or status
        # the client envelope: end-to-end wall + client-side stamps
        # (result_transfer, host_decode) the scheduler never sees
        for k, v in (env.get("stamps") or {}).items():
            phases[k] = phases.get(k, 0.0) + float(v)
        return _ledger.build_ledger(job_id, env["wall"], origin="client",
                                    status=status, phases=phases)


def _is_ddl(query: str) -> bool:
    return query.lstrip().lower().startswith("create")


def _scans_system_table(plan: Optional[LogicalPlan]) -> bool:
    from .observability.systables import SystemTableSource

    if plan is None:
        return False
    if isinstance(plan, TableScan) and \
            isinstance(plan.source, SystemTableSource):
        return True
    return any(_scans_system_table(c) for c in plan.children())


class DataFrame:
    """Lazy relational frame over a logical plan (reference:
    BallistaDataFrame, rust/client/src/context.rs:149-315)."""

    def __init__(self, ctx: BallistaContext, plan: Optional[LogicalPlan],
                 raw_sql: Optional[str] = None):
        self.ctx = ctx
        self._plan = plan
        # server-side planning: no local logical plan, the SQL text is
        # submitted with the client catalog and planned by the scheduler
        self._raw_sql = raw_sql
        # standalone mode caches the physical plan across collect() calls so
        # operator jit caches (and table caches) are reused
        self._phys = None

    # -- plan access --------------------------------------------------------

    @property
    def plan(self) -> LogicalPlan:
        if self._plan is None and self._raw_sql is not None:
            # server-planned frame used through the DataFrame API (schema,
            # verbs, count...): plan locally on demand; collect() still
            # takes the raw-SQL path
            planner = SqlPlanner(self.ctx._catalog,
                                 system_provider=self.ctx._system_source)
            self._plan = planner.plan(parse_sql(self._raw_sql))
        if self._plan is None:
            raise PlanError("this DataFrame carries no plan (DDL result)")
        return self._plan

    def schema(self) -> Schema:
        return self.plan.schema()

    def explain(self) -> str:
        from .optimizer import optimize

        return (
            "== Logical plan ==\n" + self.plan.pretty()
            + "== Optimized ==\n" + optimize(self.plan).pretty()
        )

    def explain_analyze(self) -> str:
        """Execute the frame's plan and return the physical plan text
        annotated with live operator metrics — the DataFrame face of SQL
        ``EXPLAIN ANALYZE`` (works in standalone and remote mode; the
        remote plan ships as one task, see physical/explain.py)."""
        from .logical import Explain

        out = self._with(Explain(self.plan, analyze=True)).collect()
        rows = dict(zip(out["plan_type"], out["plan"]))
        return rows.get("plan_with_metrics", "")

    def logical_plan(self) -> LogicalPlan:
        return self.plan

    # -- verbs --------------------------------------------------------------

    def _with(self, plan: LogicalPlan) -> "DataFrame":
        return DataFrame(self.ctx, plan)

    def select(self, *exprs: Union[ex.Expr, str]) -> "DataFrame":
        es = [ex.col(e) if isinstance(e, str) else e for e in exprs]
        return self._with(Projection(list(es), self.plan))

    def select_columns(self, *names: str) -> "DataFrame":
        return self.select(*names)

    def filter(self, predicate: ex.Expr) -> "DataFrame":
        return self._with(Filter(predicate, self.plan))

    where = filter

    def aggregate(self, group_by: Sequence[ex.Expr],
                  aggs: Sequence[ex.Expr]) -> "DataFrame":
        return self._with(Aggregate(list(group_by), list(aggs), self.plan))

    def sort(self, *sort_exprs: ex.Expr) -> "DataFrame":
        ses = [
            e if isinstance(e, ex.SortExpr) else ex.SortExpr(e)
            for e in sort_exprs
        ]
        return self._with(Sort(ses, self.plan))

    def limit(self, n: int) -> "DataFrame":
        return self._with(Limit(n, self.plan))

    def join(self, right: "DataFrame", on: Sequence[Tuple[str, str]],
             how: str = "inner") -> "DataFrame":
        return self._with(Join(self.plan, right.plan, list(on), how))

    def repartition(self, num_partitions: int,
                    hash_exprs: Optional[Sequence[ex.Expr]] = None) -> "DataFrame":
        return self._with(
            Repartition(self.plan, num_partitions,
                        list(hash_exprs) if hash_exprs else None)
        )

    # -- execution ----------------------------------------------------------

    def collect(self, on_progress=None):
        """Execute and return a pandas DataFrame.

        ``on_progress`` (live progress plane): a callable receiving
        progress snapshots — the ONE shape both paths share (job_id,
        fraction, eta_seconds, task counts, per-stage rows; see
        docs/observability.md). On the cluster path snapshots come from
        the scheduler's live job model via the status poll; standalone,
        a sampler thread over the executing plan's MetricsSet reports
        the same shape. Callbacks run on a background/polling thread
        and are best-effort: a raising callback is logged, never the
        query's problem. The final callback reports fraction 1.0."""
        if self._raw_sql is not None:
            import time as _time

            from .distributed.client import remote_sql_collect
            from .observability import ledger as _ledger

            sink: list = []
            jsink: list = []
            _ledger.begin_collect()
            t0 = _time.perf_counter()
            with self.ctx._track_lifecycle(jsink,
                                           self.ctx._active_job_sinks):
                out = remote_sql_collect(
                    self.ctx.host, self.ctx.port, self._raw_sql,
                    self.ctx._catalog, self.ctx.settings, metrics_out=sink,
                    job_id_out=jsink, on_progress=on_progress,
                )
            self.ctx._last_query_metrics = sink[0] if sink else None
            self.ctx._last_query_phys = None
            self.ctx._last_job_id = jsink[0] if jsink else None
            self.ctx._last_query_ledger = None
            self.ctx._last_ledger_env = {
                "wall": _time.perf_counter() - t0,
                "stamps": _ledger.take_collect(),
            }
            return out
        if self.ctx.mode == "standalone":
            out, self._phys = self.ctx._standalone_collect(
                self.plan, phys=self._phys, on_progress=on_progress)
            return out
        return self.ctx._collect(self.plan, on_progress=on_progress)

    def to_pandas(self):
        return self.collect()

    def cancel(self, reason: str = "client") -> int:
        """Cancel the context's in-flight queries (this frame's collect
        included) — see :meth:`BallistaContext.cancel`."""
        return self.ctx.cancel(reason)

    def profile(self, path: Optional[str] = None,
                label: Optional[str] = None) -> str:
        """Execute the frame under the query profiler and write ONE
        Chrome-trace/Perfetto-compatible artifact (trace spans + ingest
        phases + compile attribution + per-operator metrics + named
        wall-time lanes). Returns the artifact path.

        On the cluster path the query runs normally and the SCHEDULER
        builds the merged artifact — its own spans plus every
        executor's per-task profile window, with per-process tracks,
        task flow arrows and cluster-aggregated lanes — which this call
        fetches over the GetJobProfile RPC and writes locally."""
        if self.ctx.mode != "standalone":
            return self._profile_remote(path, label)
        from .observability import profiler as obs_profiler

        box = {}

        def run():
            out, phys = self.ctx._standalone_collect_inner(
                self.plan, phys=self._phys)
            self._phys = phys
            box["phys"] = phys
            return out

        _, artifact = obs_profiler.profile_call(
            run, label=label or "query",
            plan_getter=lambda: box.get("phys"),
            out_path=path,
            out_dir=obs_profiler.profile_dir(),
        )
        return artifact

    def _profile_remote(self, path: Optional[str],
                        label: Optional[str]) -> str:
        """Cluster df.profile(): run the query, then pull the
        scheduler-merged artifact for its job."""
        from .distributed.client import fetch_job_profile
        from .observability import profiler as obs_profiler
        from .observability.export import write_artifact_file

        self.collect()
        job_id = self.ctx._last_job_id
        if not job_id:
            raise BallistaError(
                "no job id recorded for the profiled query")
        # the client can observe job completion BEFORE the scheduler's
        # terminal-transition hook finalizes the job's profile window
        # (completion is published first so result fetches never wait
        # on observability) — briefly retry while the artifact is still
        # marked partial, or while the scheduler holds no window at all
        # yet (a job whose executors shipped no profiles creates its
        # collector slot only at finalize)
        import time as _time

        from .distributed.client import SchedulerClient
        from .errors import ClusterError

        deadline = _time.time() + 10.0
        sched = SchedulerClient(self.ctx.host, self.ctx.port)
        try:
            while True:
                try:
                    art = fetch_job_profile(self.ctx.host, self.ctx.port,
                                            job_id, client=sched)
                except ClusterError:
                    if _time.time() > deadline:
                        raise
                    _time.sleep(0.25)
                    continue
                if not (art.get("distributed") or {}).get("partial") or \
                        _time.time() > deadline:
                    break
                _time.sleep(0.25)
        finally:
            sched.close()
        if label:
            art["label"] = label
        return write_artifact_file(art, out_dir=obs_profiler.profile_dir(),
                                   out_path=path)

    def count(self) -> int:
        agg = Aggregate([], [ex.count().alias("__n")], self.plan)
        out = self.ctx._collect(agg)
        return int(out["__n"][0])

    def show(self, n: int = 20) -> None:
        print(self.limit(n).collect().to_string())
