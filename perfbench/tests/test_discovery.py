"""A table, a query with its plain reference, a configuration, a traffic mix,
a cell and a metric with a counter and a per-query span of its own are found
by file name: added to a copy of the benchmark they make a whole run, and no
file that was there is edited (only BENCHMARK.json gains entries)."""

import json
import os
import shutil
import subprocess
import sys

import run

TABLE = '''"""A table no cell had."""
import numpy as np
import pyarrow as pa

import datagen as dg

SEED_ID = 77
PRIMARY_KEY = "t_key"
ARROW_SCHEMA = pa.schema([("t_key", pa.int64()), ("t_amount", pa.float64()),
                          ("t_note", pa.string())])


def program_schema():
    from ballista_tpu import Decimal, Int64, Utf8, schema

    return schema(("t_key", Int64), ("t_amount", Decimal(2)),
                  ("t_note", Utf8))


def rows(scale):
    return max(int(100_000 * scale), 50)


def chunk(rng, lo, hi, scale):
    return {"tiny": [pa.array(np.arange(lo, hi)),
                     pa.array(dg.money(rng, hi - lo, 1.0, 900.0)),
                     dg.comments(rng, hi - lo)]}
'''

REFERENCE = '''"""Its plain reference."""
import pandas as pd

from reference import Money, load


def reference(data_dir, precision="exact"):
    m = Money(precision)
    t = load(data_dir, "tiny", ["t_key", "t_amount"])
    keep = t["t_key"] >= 10
    return pd.DataFrame({
        "n": [int(keep.sum())],
        "total": [m.value(m.total(m.col(t["t_amount"][keep])), 1)]})
'''

READER = '''"""A reader with a counter and a per-query span of its own, which reads
the raw sources the harness hands every reader."""
UNIT = "things"
CALLS = {"snapshot": 0}


def snapshot():
    CALLS["snapshot"] += 1
    return CALLS["snapshot"]


def after_query(ctx, started, seconds):
    return (ctx.last_query_ledger() is not None, started, seconds)


def read(obs):
    w = obs["window"]
    own = [q["record"]["readers"]["dummy_metric"] for q in w["queries"]]
    assert obs["snapshots"]["dummy_metric"] == (1, 2)
    assert w["started"] < w["ended"] and w["seconds"] == w["ended"] - w["started"]
    assert all(q["started"] == s and q["seconds"] == d
               for q, (_, s, d) in zip(w["queries"], own))
    assert all("ledger" in q["record"] and "stages" in q["record"]
               for q in w["queries"])
    assert obs["trace_dir"].endswith("dummy-cell") and obs["planes"]
    assert any(ev[0] == "collect:qtiny" for p in obs["planes"]
               for ln in p["lines"] for ev in ln["events"])
    return 42.0 if own and all(o[0] for o in own) else None
'''


def test_new_table_query_cell_and_metric_run_without_an_edit(tmp_path):
    pb = tmp_path / "perfbench"
    shutil.copytree(run.HERE, pb,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in pb.rglob("*") if p.is_file()}

    (pb / "tables/tiny.py").write_text(TABLE)
    (pb / "queries/qtiny.py").write_text(REFERENCE)
    (pb / "queries/qtiny.sql").write_text(
        "select count(*) as n, sum(t_amount) as total from tiny "
        "where t_key >= 10")
    (pb / "queries/qtiny.json").write_text(json.dumps({
        "sql": "qtiny.sql", "reads": {"tiny": ["t_key", "t_amount"]},
        "quotient_columns": [],
        "limits": {"exact_mismatches": 0, "sum_rel_gap": 1e-12,
                   "quotient_abs_gap": 0}}))
    config = json.loads((pb / "configs/tpch-sf3-standalone.json").read_text())
    config["scale"], config["rehearse_scale"] = 1.0, 0.02
    (pb / "configs/tiny-sf1.json").write_text(json.dumps(config))
    (pb / "traffic/tiny-2stream.json").write_text(json.dumps({
        "arrival": "closed", "streams": 2, "round": ["qtiny", "q6"],
        "parameters": "fixed"}))
    (pb / "cells/dummy-cell.json").write_text(json.dumps({
        "end_to_end": ["queries_per_hour", "setup_s"],
        "per_layer": ["dummy_metric", "syncs_per_query"]}))
    (pb / "metrics/dummy_metric.py").write_text(READER)
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": "tiny-sf1", "source": "test", "reduced": ["scale"],
        "file": "perfbench/configs/tiny-sf1.json", "why": "test"})
    bench["workloads"].append({
        "name": "dummy-cell", "config": "tiny-sf1",
        "traffic": "tiny-2stream", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=run.ROOT)
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--rehearse", "--workload",
         "dummy-cell", "--seed", str(2**31 + 3), "--seconds", "0.5",
         "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()
             if ln.startswith("{")]
    data = next(ln for ln in lines if ln.get("phase") == "data")
    assert sorted(data["made"]) == ["lineitem", "tiny"]
    assert (tmp_path / data["dir"] / "tiny" / "part-3.parquet").exists()
    checks = {ln["query"]: ln for ln in lines if ln.get("phase") == "check"}
    assert set(checks) == {"qtiny", "q6"}
    assert all(c["ok"] and c["answers"] >= 2 for c in checks.values())
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"]["dummy_metric"] == {"value": 42.0,
                                               "unit": "things"}
    assert last["metrics"]["syncs_per_query"]["value"] > 0
    # the cells that were there are found as before, and no file changed
    assert run.find_cell("served-join")["traffic"]["round"] == ["q3", "q14"]
    assert all(p.read_bytes() == data for p, data in before.items())
