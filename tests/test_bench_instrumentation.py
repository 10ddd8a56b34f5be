"""Gate tests for ``chip_smoke.py``: it refuses to run without a chip,
and its ``--rehearse`` dry run drives every phase on the CPU.
"""

import os
import sys


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
