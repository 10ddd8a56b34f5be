"""Gate test for bench.py's per-stage instrumentation.

Round-4 regression: the scanner's return arity changed (validity masks
added) and ``bench.instrument_q1`` silently broke — the round's bench
record held ``stages_error`` instead of the parse/h2d/kernel decomposition.
Nothing in the gate exercised the instrumentation, so this test runs it
end-to-end on tiny data (SF0.002, 2 partitions so the multi-partition
concat path is covered too) and asserts the stage fields are populated.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(scope="module")
def tiny_data(tmp_path_factory):
    from benchmarks.tpch import datagen

    data_dir = str(tmp_path_factory.mktemp("bench_instr"))
    datagen.generate(data_dir, scale=0.002, num_parts=2)
    return data_dir


def test_instrument_q1_populates_stages(tiny_data):
    import bench

    out = bench.instrument_q1(tiny_data, runs=1)
    # parse / h2d / kernel triplet must all be present and positive
    for key in ("parse_s", "parse_mb_per_s", "h2d_s", "rows",
                "kernel_s", "kernel_rows_per_s", "kernel_aot_compile_s"):
        assert key in out, f"missing stage field {key}: {out}"
    assert out["rows"] > 0
    assert out["kernel_s"] > 0
    assert out["kernel_rows_per_s"] > 0


def test_cold_phase_split_fields(tiny_data, monkeypatch):
    """bench.cold_phase_split (the source of the parse_seconds /
    h2d_seconds / execute_seconds JSON fields) must populate all phase
    fields, and — with the ingest pipeline gated off, where phase time
    is consumer-thread time — they must sum to the wall time."""
    from ballista_tpu import ingest

    monkeypatch.setenv("BALLISTA_INGEST_THREADS", "1")
    monkeypatch.setenv("BALLISTA_PREFETCH_BATCHES", "0")
    ingest.reconfigure()
    try:
        import bench
        from ballista_tpu.client import BallistaContext
        from benchmarks.tpch.schema_def import TPCH_PKS, TPCH_SCHEMAS

        ctx = BallistaContext.standalone()
        ctx.register_tbl("lineitem", os.path.join(tiny_data, "lineitem"),
                         TPCH_SCHEMAS["lineitem"],
                         primary_key=TPCH_PKS["lineitem"])
        sql = open(os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks", "tpch", "queries",
                                "q1.sql")).read()
        _, phases = bench.cold_phase_split(
            lambda: ctx.sql(sql).collect())
    finally:
        monkeypatch.undo()
        ingest.reconfigure()
    for key in ("wall_seconds", "parse_seconds", "h2d_seconds",
                "execute_seconds"):
        assert key in phases, f"missing {key}: {phases}"
        assert phases[key] >= 0
    assert phases["parse_seconds"] > 0
    assert phases["h2d_seconds"] > 0
    total = (phases["parse_seconds"] + phases["h2d_seconds"]
             + phases["execute_seconds"])
    wall = phases["wall_seconds"]
    # serial mode: parse + h2d + execute ≈ wall (rounding noise only)
    assert abs(total - wall) <= max(0.05 * wall, 0.02), phases


def _run_chip_smoke(*argv):
    import subprocess

    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py"), *argv],
        capture_output=True, text=True, timeout=600, env=env)


def test_chip_smoke_refuses_without_tpu():
    """The driver's contract: where JAX finds no accelerator the smoke
    exits non-zero and prints no result — there is no CPU fallback."""
    out = _run_chip_smoke()
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_rehearsal_runs_every_phase(tmp_path):
    """``--rehearse`` drives the SAME flow on the CPU at a tiny scale
    (the dry run to make before a chip call): every query of both paths
    must match the oracle, the native scanner and data plane must be
    the ones in use, and the last line must never claim ``ok``."""
    import json

    out = _run_chip_smoke("--rehearse", "--scale", "0.002",
                          "--data", str(tmp_path / "d"))
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()]
    phases = {ln.get("phase"): ln for ln in lines[:-1]}
    for phase in ("standalone.q1", "standalone.q6", "standalone.q14",
                  "served.q1", "served.q14"):
        assert phases[phase]["equals_oracle"] is True
        assert len(phases[phase]["warm_seconds"]) == 3
        assert phases[phase]["cold"]["backend_compiles"] >= 0
    assert phases["setup.scanner"]["scanner"] == "native"
    assert phases["setup.data_plane"]["data_plane"] == "native"
    assert lines[-1]["ok"] is False and lines[-1]["rehearsal"] == "passed"
    assert lines[-1]["device"]["platform"] == "cpu"


def test_bench_refuses_without_chip():
    """bench.py without ``--cpu`` on a machine with no chip: non-zero
    exit and no metric line (``--cpu`` is the only way onto the CPU)."""
    import subprocess

    repo = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode != 0
    assert out.stdout.strip() == "", out.stdout
