"""Process-level integration: the real scheduler and executor BINARIES
(separate processes, real gRPC control plane, real socket data plane)
serve a SQL query end to end — the role docker-compose integration
plays for the reference (dev/integration-tests.sh), without docker.
With ``BALLISTA_PROFILE`` on the scheduler the run also gates the
distributed profiler: executors ship per-task profile windows over the
wire and the scheduler merges them into one Chrome-trace artifact with
a REAL process track per executor pid."""

import json
import os
import re
import signal
import subprocess
import time

import numpy as np
import pytest

from ballista_tpu import schema, Int64, Utf8
from tests.procutil import (http_get, spawn_module as _spawn,
                            wait_healthz)


def _health_port(proc) -> int:
    line = proc.wait_for(lambda ln: "health plane on" in ln)
    m = re.search(r"health plane on [^:]+:(\d+)", line)
    assert m, f"no health port in output: {line!r}"
    return int(m.group(1))


def test_binaries_end_to_end(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    repo = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    # distributed profiler: the scheduler merges its own spans with the
    # task profile windows the executor binaries ship over the wire
    profile_dir = tmp_path / "profiles"
    sched_env = dict(env)
    sched_env["BALLISTA_PROFILE"] = str(profile_dir)
    # the scheduler must never initialise a JAX backend (on a one-chip
    # host it shares the machine with the ONE executor process that
    # owns the chip): with a platform that does not exist, any backend
    # init in its planning / profile-merge / health paths raises and
    # the query below fails
    sched_env["JAX_PLATFORMS"] = "no_such_backend"

    procs = []
    try:
        sched = _spawn(["ballista_tpu.distributed.scheduler_main",
                        "--bind-host", "localhost", "--port", "0"],
                       sched_env)
        procs.append(sched)
        line = sched.wait_for(lambda ln: "listening on" in ln)
        m = re.search(r"listening on [^:]+:(\d+)", line)
        assert m, f"no port in scheduler output: {line!r}"
        port = int(m.group(1))
        # readiness via the health plane, not sleeps/log scraping
        sched_health = _health_port(sched)
        assert wait_healthz(sched_health)["role"] == "scheduler"

        exec_health = []
        for i in range(2):
            e = _spawn(["ballista_tpu.distributed.executor_main",
                        "--scheduler-host", "localhost",
                        "--scheduler-port", str(port),
                        "--work-dir", str(tmp_path / f"w{i}"),
                        "--concurrent-tasks", "1",
                        "--num-devices", "1"], env)
            procs.append(e)
            exec_health.append(_health_port(e))
        for hp in exec_health:
            assert wait_healthz(hp)["role"] == "executor"

        # a DIRECTORY of part files -> multi-partition scan stage, so
        # with one task slot per executor both executors serve tasks
        data = tmp_path / "t"
        data.mkdir()
        for p in range(6):
            (data / f"part-{p}.tbl").write_text(
                "".join(f"{i}|k{i % 3}|\n"
                        for i in range(p * 15, (p + 1) * 15)))

        from ballista_tpu.client import BallistaContext
        from ballista_tpu.io import TblSource

        ctx = BallistaContext.remote("localhost", port)
        ctx.register_source(
            "t", TblSource(str(data), schema(("a", Int64), ("c", Utf8)))
        )
        got = ctx.sql(
            "select c, sum(a) as s, count(*) as n from t group by c order by c"
        ).collect()
        a = np.arange(90)
        for i in range(3):
            m_ = a % 3 == i
            assert got["c"][i] == f"k{i}"
            assert int(got["s"][i]) == int(a[m_].sum())
            assert int(got["n"][i]) == int(m_.sum())

        # the REAL binaries serve the health plane: executor heartbeat
        # gauges aggregated on the scheduler, job counters advanced
        text = http_get(sched_health, "/metrics")
        assert "ballista_executors_live 2" in text
        assert "ballista_jobs_completed_total 1" in text
        assert "ballista_executor_rss_bytes{" in text

        # merged per-job artifact: one file, valid Chrome-trace JSON,
        # with the scheduler track and BOTH executor processes (real
        # distinct pids) as their own tracks, task flow arrows included.
        # Job completion is published to the client BEFORE the
        # scheduler's terminal hook writes the artifact — poll briefly.
        deadline = time.time() + 30
        files = []
        while time.time() < deadline and not files:
            files = list(profile_dir.glob("ballista-profile-job-*.json"))
            if not files:
                time.sleep(0.2)
        assert len(files) == 1, files
        art = json.load(open(files[0]))
        assert art["traceEvents"] and art.get("displayTimeUnit") == "ms"
        tracks = [ev["args"]["name"] for ev in art["traceEvents"]
                  if ev.get("ph") == "M" and ev["name"] == "process_name"]
        assert any(t.startswith("scheduler") for t in tracks), tracks
        exec_tracks = [t for t in tracks if t.startswith("executor ")]
        assert len(exec_tracks) >= 2, tracks
        # distinct OS pids on the executor tracks (real processes)
        exec_pids = {re.search(r"pid (\d+)", t).group(1)
                     for t in exec_tracks}
        assert len(exec_pids) >= 2, tracks
        assert any(ev.get("ph") == "s" for ev in art["traceEvents"])
        assert set(art["lanes"]) and art["wall_seconds"] > 0
        # /debug/profile/<job_id> serves the same artifact from the
        # scheduler binary's health plane
        dbg = json.loads(http_get(sched_health, "/debug/queries"))
        job_entries = [q for q in dbg["queries"] if "job_id" in q]
        assert job_entries and job_entries[-1].get("plan_digest")
        served = json.loads(http_get(
            sched_health, f"/debug/profile/{job_entries[-1]['job_id']}"))
        assert served["distributed"]["job_id"] == \
            job_entries[-1]["job_id"]
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_flight_frontend_against_real_cluster(tmp_path):
    """A FOREIGN Arrow Flight client (stock pyarrow, no ballista code)
    runs DDL + a query against the scheduler binary's --flight-port:
    the reference JDBC driver's jdbc:arrow://host:port flow, end to end
    through the real cluster (scheduler + executor processes)."""
    paflight = pytest.importorskip("pyarrow.flight")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    repo = os.path.join(os.path.dirname(__file__), "..")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    try:
        sched = _spawn(["ballista_tpu.distributed.scheduler_main",
                        "--bind-host", "localhost", "--port", "0",
                        "--flight-port", "0"], env)
        procs.append(sched)
        line = sched.wait_for(lambda ln: "listening on" in ln)
        m = re.search(r"listening on [^:]+:(\d+)", line)
        assert m, f"no port in scheduler output: {line!r}"
        fline = sched.wait_for(lambda ln: "Flight SQL endpoint on" in ln)
        fm = re.search(r"Flight SQL endpoint on [^:]+:(\d+)", fline)
        assert fm, f"no flight port in scheduler output: {fline!r}"
        fport = int(fm.group(1))

        e = _spawn(["ballista_tpu.distributed.executor_main",
                    "--scheduler-host", "localhost",
                    "--scheduler-port", m.group(1),
                    "--work-dir", str(tmp_path / "w0"),
                    "--num-devices", "1"], env)
        procs.append(e)
        wait_healthz(_health_port(e))

        data = tmp_path / "t.tbl"
        data.write_text("".join(f"{i}|k{i % 3}|\n" for i in range(60)))

        client = paflight.connect(f"grpc://localhost:{fport}")
        ddl = (f"CREATE EXTERNAL TABLE t (a BIGINT, c VARCHAR) "
               f"STORED AS TBL LOCATION '{data}'")
        status = client.do_get(paflight.Ticket(ddl.encode())).read_all()
        assert status["status"][0].as_py() == "OK"
        got = client.do_get(paflight.Ticket(
            b"select c, sum(a) as s from t group by c order by c"
        )).read_all().to_pandas()
        a = np.arange(60)
        assert list(got["c"]) == ["k0", "k1", "k2"]
        for i in range(3):
            assert int(got["s"][i]) == int(a[a % 3 == i].sum())
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
