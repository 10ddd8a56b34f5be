"""SQL-queryable ``system.*`` tables + durable query history (ISSUE 8).

Covers: SELECT over every system table on the standalone path (queries
ring with status/wall/rows/digest, flight-recorder lanes, deferred
operator metrics, compile-governor entries, the settings registry, the
self executor row); the shared-record contract with ``/debug/queries``;
the durable history log (rotation, restart survival via a subprocess);
LocalCluster e2e (``system.executors`` lists both executors with
heartbeat resources, a slow query lands in ``system.queries`` with its
plan digest + artifact path, lanes annotate cluster jobs); serde of
materialized system scans; the knob-docs lint; and the < 5% warm-q1
overhead gate extended to the history-log write path."""

import json
import os
import subprocess
import sys
import time

import pytest

from ballista_tpu.client import BallistaContext
from ballista_tpu.datatypes import Float64, Int64, Utf8, schema
from ballista_tpu.observability import systables
from ballista_tpu.observability.export import LANE_NAMES

REPO = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def ctx():
    c = BallistaContext.standalone()
    c.register_memtable(
        "t", schema(("k", Utf8), ("a", Int64), ("b", Float64)),
        {"k": ["x", "y", "z"] * 20,
         "a": list(range(60)),
         "b": [float(i) / 4 for i in range(60)]},
    )
    return c


@pytest.fixture
def clean_env():
    keys = ("BALLISTA_QUERY_LOG_DIR", "BALLISTA_QUERY_LOG_MAX_MB",
            "BALLISTA_PROFILE", "BALLISTA_SLOW_QUERY_SECS",
            "BALLISTA_SLOW_QUERY_DIR", "BALLISTA_TRACE",
            "BALLISTA_TRACE_FILE")
    saved = {k: os.environ.get(k) for k in keys}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def _fresh_select(ctx, sql):
    """System-table scans rebuild rows per collect, but assertions about
    queries recorded BETWEEN two identical SELECTs need a fresh plan —
    drop the SQL plan cache to keep the test honest about that."""
    ctx._plan_cache.clear()
    return ctx.sql(sql).collect()


# ---------------------------------------------------------------------------
# standalone path
# ---------------------------------------------------------------------------


def test_system_queries_standalone(ctx, clean_env):
    out = ctx.sql(
        "SELECT k, sum(a) AS s FROM t GROUP BY k ORDER BY k").collect()
    assert len(out) == 3
    q = _fresh_select(
        ctx, "SELECT job_id, plan_digest, status, wall_seconds, "
             "output_rows, origin FROM system.queries")
    # the SELECT over system.queries is itself an in-flight
    # status="running" row — assert on the last *completed* query
    done = q[q["status"] == "completed"]
    assert len(done) >= 1
    row = done.iloc[-1]
    assert row["status"] == "completed"
    assert row["origin"] == "standalone"
    assert row["job_id"].startswith("local-")
    assert len(row["plan_digest"]) == 12
    assert row["wall_seconds"] > 0
    assert row["output_rows"] == 3
    # ORDER BY over a system table is an ordinary plan
    q2 = _fresh_select(
        ctx, "SELECT job_id, wall_seconds FROM system.queries "
             "ORDER BY wall_seconds DESC LIMIT 3")
    assert len(q2) >= 1
    assert list(q2["wall_seconds"]) == sorted(q2["wall_seconds"],
                                              reverse=True)


def test_system_query_lanes_standalone(ctx, clean_env):
    ctx.sql("SELECT sum(a) AS s FROM t").collect()
    lanes = _fresh_select(
        ctx, "SELECT job_id, lane, seconds, fraction "
             "FROM system.query_lanes")
    assert len(lanes) >= len(LANE_NAMES)
    got = set(lanes["lane"])
    assert got <= set(LANE_NAMES)
    # every recorded query carries the full lane set
    last_job = lanes.iloc[-1]["job_id"]
    per_query = lanes[lanes["job_id"] == last_job]
    assert set(per_query["lane"]) == set(LANE_NAMES)
    assert (per_query["seconds"] >= 0).all()


def test_system_operators_standalone(ctx, clean_env):
    ctx.sql("SELECT k, sum(a) AS s FROM t GROUP BY k").collect()
    ops = _fresh_select(
        ctx, "SELECT operator, metric, value FROM system.operators "
             "WHERE metric = 'output_rows'")
    assert len(ops) >= 1
    scans = ops[ops["operator"].str.startswith("ScanExec: t")]
    assert len(scans) >= 1 and float(scans.iloc[-1]["value"]) == 60.0


def test_system_operators_stale_epoch_dropped(ctx, clean_env):
    # two un-harvested collects of the SAME cached plan: the second
    # run's metric reset bumps the plan's epoch, so the FIRST run's
    # deferred snapshot must decline (its values were clobbered) while
    # the second harvests fine — never the second run's numbers under
    # the first run's job id
    df = ctx.sql("SELECT sum(b) AS s FROM t")
    df.collect()
    job_a = systables.process_query_log().snapshot()["queries"][-1]["job_id"]
    df.collect()
    job_b = systables.process_query_log().snapshot()["queries"][-1]["job_id"]
    assert job_a != job_b
    jobs = {r["job_id"] for r in systables.operator_store().rows()}
    assert job_b in jobs
    assert job_a not in jobs


def test_system_settings(ctx, clean_env, monkeypatch):
    s = _fresh_select(
        ctx, "SELECT name, value, source, description "
             "FROM system.settings WHERE name = 'BALLISTA_FUSION'")
    assert len(s) == 1
    assert s.iloc[0]["value"] == "on" and s.iloc[0]["source"] == "default"
    monkeypatch.setenv("BALLISTA_FUSION", "0")
    s = _fresh_select(
        ctx, "SELECT value, source FROM system.settings "
             "WHERE name = 'BALLISTA_FUSION'")
    assert s.iloc[0]["value"] == "0" and s.iloc[0]["source"] == "env"
    # registry completeness: every registered knob appears exactly once
    all_rows = _fresh_select(ctx, "SELECT name FROM system.settings")
    names = list(all_rows["name"])
    for knob in systables.KNOBS:
        assert names.count(knob) == 1


# The ten knobs PR 30 removed: three switched on mechanisms that are
# gone (AOT export, prewarm, the Pallas aggregate), seven had one value
# in use and became constants beside their reader. name -> (value a
# user might still export, what the reader returns whatever it is).
_REMOVED_KNOBS = {
    "BALLISTA_FUSION_AOT_DIR": ("{tmp}/aot", False),   # dir is created
    "BALLISTA_PREWARM": ("1", False),       # a prewarm counter exists
    "BALLISTA_PALLAS": ("interpret", False),     # pallas gets imported
    "BALLISTA_JOIN_SYNC_WINDOW": ("2", 8),
    "BALLISTA_JOIN_SYNC_WINDOW_BYTES": ("4096", 1 << 30),
    "BALLISTA_JOIN_SWAP": ("off", True),
    "BALLISTA_NARROW_WIRE": ("on", False),  # auto: off on the CPU
    "BALLISTA_JIT_CACHE_ENTRIES": ("3", 1024),
    "BALLISTA_JIT_TRACES_PER_ENTRY": ("1", 128),
    "BALLISTA_SCAN_CHUNK_BYTES": ("64", 1 << 30),
}

_REMOVED_KNOBS_PROBE = """
import json, os, sys
import jax.numpy as jnp
from ballista_tpu import columnar
from ballista_tpu.compile import compile_stats, governed, governor as gov
from ballista_tpu.compile.governor import GovernedFunction, JIT_CACHE_ENTRIES
from ballista_tpu.io import text
from ballista_tpu.kernels.aggregate import AggInput, dense_grouped_aggregate
from ballista_tpu.observability import systables
from ballista_tpu.physical import join
from ballista_tpu.physical.planner import PlannerOptions

fn = governed(("agg.grouped", "probe"), lambda: (
    lambda g, live, v: dense_grouped_aggregate(
        g, live, [AggInput("sum", v, None)], 4).aggregates[0]))
out = fn(jnp.arange(8, dtype=jnp.int32) % 4, jnp.ones(8, bool),
         jnp.arange(8, dtype=jnp.int64))
assert [int(x) for x in out] == [4, 6, 8, 10]
print(json.dumps({
    "settings": [r["name"] for r in systables.settings_rows()],
    "BALLISTA_FUSION_AOT_DIR":
        os.path.exists(os.environ["BALLISTA_FUSION_AOT_DIR"]),
    "BALLISTA_PREWARM": any("prewarm" in k for k in compile_stats()),
    "BALLISTA_PALLAS": any("pallas" in m for m in sys.modules),
    "BALLISTA_JOIN_SYNC_WINDOW": join._SYNC_WINDOW,
    "BALLISTA_JOIN_SYNC_WINDOW_BYTES": join._SYNC_WINDOW_BYTES,
    "BALLISTA_JOIN_SWAP": PlannerOptions.from_settings({}).join_swap,
    "BALLISTA_NARROW_WIRE": columnar._narrow_wire_enabled(),
    "BALLISTA_JIT_CACHE_ENTRIES": JIT_CACHE_ENTRIES,
    "BALLISTA_JIT_TRACES_PER_ENTRY": GovernedFunction._TRACES_PER_ENTRY,
    "BALLISTA_SCAN_CHUNK_BYTES": text.STREAM_CHUNK_BYTES,
}))
"""


@pytest.fixture(scope="module")
def removed_knobs_probe(tmp_path_factory):
    """One fresh process with all ten variables exported (import-time
    readers included), reporting what each reader returns."""
    tmp = str(tmp_path_factory.mktemp("removed_knobs"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for name, (value, _) in _REMOVED_KNOBS.items():
        env[name] = value.format(tmp=tmp)
    out = subprocess.run([sys.executable, "-c", _REMOVED_KNOBS_PROBE],
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("name", sorted(_REMOVED_KNOBS))
def test_removed_knob_has_no_row_and_no_effect(removed_knobs_probe, name):
    assert name not in systables.KNOBS
    assert name not in removed_knobs_probe["settings"]
    assert removed_knobs_probe[name] == _REMOVED_KNOBS[name][1]


def test_system_compile_and_executors(ctx, clean_env):
    ctx.sql("SELECT k, sum(a) AS s FROM t GROUP BY k").collect()
    c = _fresh_select(
        ctx, "SELECT namespace, signature, calls, compiles "
             "FROM system.compile")
    assert len(c) >= 1 and (c["calls"] >= 0).all()
    e = _fresh_select(ctx, "SELECT * FROM system.executors")
    assert len(e) == 1
    row = e.iloc[0]
    assert row["executor_id"] == "standalone"
    assert row["rss_bytes"] > 0 and row["num_devices"] >= 1


def test_dataframe_api_and_explain(ctx, clean_env):
    ctx.sql("SELECT sum(a) AS s FROM t").collect()
    df = ctx.table("system.settings")
    out = df.collect()
    assert len(out) == len(systables.settings_rows())
    plan = ctx.sql("EXPLAIN SELECT * FROM system.queries").collect()
    assert "TableScan: system.queries" in plan["plan"][0]
    txt = ctx.sql(
        "EXPLAIN ANALYZE SELECT count(*) AS n FROM system.settings"
    ).collect()
    rendered = dict(zip(txt["plan_type"], txt["plan"]))
    assert "ScanExec: system.settings" in rendered["plan_with_metrics"]


def test_system_plans_not_cached_joins_stay_fresh(ctx, clean_env):
    # a join over system tables materializes its build side per plan
    # instance: ctx.sql must NOT serve a cached plan for system scans,
    # or a re-issued query would join fresh probe rows against the
    # FIRST collect's frozen build-side snapshot
    sql = ("SELECT q.job_id FROM system.queries q, system.query_lanes l "
           "WHERE q.job_id = l.job_id")
    ctx.sql("SELECT sum(a) AS s FROM t").collect()
    ctx.sql(sql).collect()
    assert sql not in ctx._plan_cache
    ctx.sql("SELECT sum(b) AS s2 FROM t").collect()
    new_job = systables.process_query_log().snapshot()["queries"][-1]["job_id"]
    second = ctx.sql(sql).collect()  # same SQL text, no cache clearing
    assert new_job in set(second["q__job_id"])


def test_failed_query_recorded(ctx, clean_env, tmp_path):
    # valid plan (the file exists at registration), fails at EXECUTION:
    # the file vanishes before the scan runs
    path = tmp_path / "ghost.csv"
    path.write_text("k,a\nx,1\n")
    ctx.register_csv("ghost", str(path), schema(("k", Utf8), ("a", Int64)))
    path.unlink()
    with pytest.raises(Exception):
        ctx.sql("SELECT sum(a) AS s FROM ghost").collect()
    q = _fresh_select(
        ctx, "SELECT status, error FROM system.queries "
             "WHERE status = 'failed'")
    assert len(q) >= 1
    assert q.iloc[-1]["error"]


# ---------------------------------------------------------------------------
# shared-record contract (/debug/queries <-> system.queries)
# ---------------------------------------------------------------------------


def test_debug_queries_shares_record_shape(ctx, clean_env):
    ctx.sql("SELECT sum(a) AS s FROM t").collect()
    snap = systables.process_query_log().snapshot()
    entry = snap["queries"][-1]
    # the satellite contract: ring entries carry status, wall_seconds
    # and output_rows — the same fields system.queries serves
    assert entry["status"] == "completed"
    assert entry["state"] == "completed"  # legacy alias intact
    assert entry["wall_seconds"] > 0
    assert entry["output_rows"] == 1
    assert set(entry.get("lanes", {})) <= set(LANE_NAMES)
    q = _fresh_select(
        ctx, "SELECT job_id, wall_seconds FROM system.queries")
    assert entry["job_id"] in set(q["job_id"])
    match = q[q["job_id"] == entry["job_id"]]
    assert float(match.iloc[0]["wall_seconds"]) == \
        pytest.approx(entry["wall_seconds"], abs=1e-3)


# ---------------------------------------------------------------------------
# durable history log
# ---------------------------------------------------------------------------


def test_history_log_rotation(tmp_path):
    log = systables.QueryHistoryLog(str(tmp_path), max_bytes=5000)
    for i in range(200):
        log.append({"job_id": f"j{i}", "status": "completed",
                    "wall_seconds": 0.1, "pad": "x" * 80})
    main = os.path.join(str(tmp_path), "query_history.jsonl")
    rotated = main + ".1"
    assert os.path.exists(main) and os.path.exists(rotated)
    assert os.path.getsize(main) <= 5000 + 200
    assert os.path.getsize(rotated) <= 5000 + 200
    records = log.read()
    # newest records survive; last-line-per-job dedup holds
    assert records[-1]["job_id"] == "j199"
    ids = [r["job_id"] for r in records]
    assert len(ids) == len(set(ids))


def test_history_dedups_enriched_lines(tmp_path):
    log = systables.QueryHistoryLog(str(tmp_path))
    log.append({"job_id": "a", "status": "completed", "wall_seconds": 1})
    log.append({"job_id": "a", "status": "completed", "wall_seconds": 1,
                "lanes": {"parse": 0.5}})
    recs = log.read()
    assert len(recs) == 1 and recs[0]["lanes"] == {"parse": 0.5}


def test_history_survives_process_restart(ctx, clean_env, tmp_path,
                                          monkeypatch):
    """The acceptance gate: rows written under BALLISTA_QUERY_LOG_DIR
    are SELECTable from a FRESH process (its in-memory ring is empty,
    so everything must come from disk)."""
    monkeypatch.setenv("BALLISTA_QUERY_LOG_DIR", str(tmp_path))
    ctx.sql("SELECT k, sum(a) AS s FROM t GROUP BY k").collect()
    snap = systables.process_query_log().snapshot()
    job_id = snap["queries"][-1]["job_id"]
    hist = os.path.join(str(tmp_path), "query_history.jsonl")
    assert os.path.exists(hist)
    code = (
        "import json, os\n"
        "from ballista_tpu.client import BallistaContext\n"
        "ctx = BallistaContext.standalone()\n"
        "q = ctx.sql('SELECT job_id, status, output_rows, origin '\n"
        "            'FROM system.queries').collect()\n"
        "print('ROWS=' + json.dumps(q.to_dict('records')))\n"
    )
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "BALLISTA_QUERY_LOG_DIR": str(tmp_path)})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(l for l in out.stdout.splitlines()
                if l.startswith("ROWS="))
    rows = json.loads(line[len("ROWS="):])
    match = [r for r in rows if r["job_id"] == job_id]
    assert match, rows
    assert match[0]["status"] == "completed"
    assert match[0]["output_rows"] == 3
    assert match[0]["origin"] == "history"


# ---------------------------------------------------------------------------
# serde: materialized system scans cross the wire
# ---------------------------------------------------------------------------


def test_system_source_serde_roundtrip(ctx, clean_env):
    from ballista_tpu import serde

    ctx.sql("SELECT sum(a) AS s FROM t").collect()
    src = systables.SystemTableSource("system.queries")
    p = serde.source_to_proto(src)
    assert p.kind == "system" and p.path == "system.queries"
    back = serde.source_from_proto(p)
    rows = back.current_rows()
    assert rows and rows[-1]["status"] == "completed"
    # deserialized sources scan the MATERIALIZED snapshot (frozen at
    # serialization time), with NULLs masked
    batches = list(back.scan(0))
    assert batches and int(batches[0].num_rows) == len(rows)


# ---------------------------------------------------------------------------
# cluster path (satellite: LocalCluster e2e)
# ---------------------------------------------------------------------------


def test_cluster_system_tables_end_to_end(clean_env, tmp_path):
    from ballista_tpu.distributed.executor import LocalCluster
    from tests.procutil import http_get

    os.environ["BALLISTA_SLOW_QUERY_SECS"] = "0.0"  # everything is slow
    os.environ["BALLISTA_PROFILE"] = str(tmp_path / "profiles")
    os.environ["BALLISTA_QUERY_LOG_DIR"] = str(tmp_path / "qlog")
    csv = tmp_path / "t.csv"
    with open(csv, "w") as f:
        f.write("k,a\n")
        for i in range(40):
            f.write(f"{'xy'[i % 2]},{i}\n")

    cluster = LocalCluster(num_executors=2, metrics_port=0)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port)
        ctx.register_csv("t", str(csv), schema(("k", Utf8), ("a", Int64)))

        # system.executors BEFORE any job: both executors, heartbeat
        # resource columns populated (scheduler-side state)
        deadline = time.time() + 30
        while time.time() < deadline:
            e = _fresh_select(ctx, "SELECT * FROM system.executors")
            if len(e) == 2 and (e["rss_bytes"] > 0).all():
                break
            time.sleep(0.2)
        assert len(e) == 2, e
        assert (e["rss_bytes"] > 0).all()
        assert set(e.columns) >= {"executor_id", "host", "port",
                                  "num_devices", "rss_bytes",
                                  "device_bytes", "inflight_tasks",
                                  "ingest_pool_depth", "peak_host_bytes"}

        out = ctx.sql(
            "SELECT k, sum(a) AS s FROM t GROUP BY k ORDER BY k"
        ).collect()
        assert list(out["s"]) == [380, 400]
        job_id = ctx._last_job_id
        assert job_id

        # the slow query (threshold 0) lands in system.queries with its
        # plan digest; the deferred worker attaches the merged profile
        # artifact path + lanes shortly after the terminal transition
        row = lanes = None
        deadline = time.time() + 30
        while time.time() < deadline:
            q = _fresh_select(
                ctx, "SELECT job_id, status, plan_digest, output_rows, "
                     "profile_artifact, origin FROM system.queries")
            match = q[q["job_id"] == job_id]
            pa = match.iloc[0]["profile_artifact"] if len(match) else None
            if isinstance(pa, str) and pa:
                row = match.iloc[0]
                lanes = _fresh_select(
                    ctx, "SELECT job_id, lane, seconds "
                         "FROM system.query_lanes")
                lanes = lanes[lanes["job_id"] == job_id]
                if len(lanes):
                    break
            time.sleep(0.25)
        assert row is not None, "job never got its artifact annotation"
        assert row["status"] == "completed"
        assert row["origin"] == "cluster"
        assert len(row["plan_digest"]) == 12
        assert int(row["output_rows"]) == 2
        assert os.path.exists(row["profile_artifact"])
        assert set(lanes["lane"]) == set(LANE_NAMES)

        # cluster operator metrics are queryable
        ops = _fresh_select(
            ctx, "SELECT job_id, operator, value FROM system.operators "
                 "WHERE metric = 'output_rows'")
        assert job_id in set(ops["job_id"])

        # history log got the cluster job (restart durability is the
        # standalone subprocess test's job; here: the line exists and
        # carries the digest)
        hist = systables.QueryHistoryLog(
            str(tmp_path / "qlog")).read()
        match = [r for r in hist if r.get("job_id") == job_id]
        assert match and match[-1]["plan_digest"] == row["plan_digest"]

        # /debug/queries serves the SAME record shape (shared builder):
        # status + wall_seconds + output_rows on the ring entries
        dbg = json.loads(http_get(cluster.scheduler_health_port,
                                  "/debug/queries"))
        entry = next(d for d in dbg["queries"]
                     if d.get("job_id") == job_id)
        assert entry["status"] == "completed"
        assert entry["wall_seconds"] > 0
        assert entry["output_rows"] == 2
        assert dbg["slow_queries"], "threshold 0 query missed slow ring"
    finally:
        cluster.shutdown()
        for k in ("BALLISTA_SLOW_QUERY_SECS", "BALLISTA_PROFILE",
                  "BALLISTA_QUERY_LOG_DIR"):
            os.environ.pop(k, None)


# ---------------------------------------------------------------------------
# lint + overhead gate
# ---------------------------------------------------------------------------


def test_knob_docs_lint():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "dev", "check_knob_docs.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr + out.stdout


def test_query_history_overhead_q1_under_5pct(tmp_path_factory,
                                              clean_env):
    """Warm q1 with the query-history log ENABLED (every collect
    appends a JSON line) stays within 5% of disabled — the
    drift-cancelling scheme from the PR 1/5 gates (alternating
    interleaved samples, medians, retries). The always-on parts of the
    recorder (ring record, lanes from the flight recorder) are present
    in BOTH samples by design — this gates the satellite's target, the
    history WRITE path."""
    from benchmarks.tpch import datagen
    from benchmarks.tpch.schema_def import register_tpch

    data_dir = str(tmp_path_factory.mktemp("tpch_hist"))
    log_dir = str(tmp_path_factory.mktemp("qlog"))
    datagen.generate(data_dir, scale=0.01, num_parts=1)
    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    qdir = os.path.join(REPO, "benchmarks", "tpch", "queries")
    df = ctx.sql(open(os.path.join(qdir, "q1.sql")).read())
    df.collect()  # warm: jit compile + table caches

    def set_enabled(on: bool):
        if on:
            os.environ["BALLISTA_QUERY_LOG_DIR"] = log_dir
        else:
            os.environ.pop("BALLISTA_QUERY_LOG_DIR", None)

    def sample(on: bool):
        set_enabled(on)
        t0 = time.perf_counter()
        for _ in range(3):
            df.collect()
        return time.perf_counter() - t0

    try:
        sample(True)
        sample(False)

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

        for _attempt in range(3):
            t_off, t_on = measure()
            if t_on <= t_off * 1.05 + 2e-3:
                break
        else:
            overhead = (t_on - t_off) / t_off
            raise AssertionError(
                f"query-history overhead {overhead:.1%} "
                f"(on={t_on:.4f}s off={t_off:.4f}s)")
        # the enabled samples really wrote history lines
        hist = systables.QueryHistoryLog(log_dir).read()
        assert hist and hist[-1]["status"] == "completed"
    finally:
        set_enabled(False)
