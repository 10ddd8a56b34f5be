"""Whole-stage fusion (physical/fusion.py): determinism, plan shape,
re-plan cache reuse, the distinct-count kernel, and the program-count
regression gate.

The fusion pass reorders NOTHING — TPC-H results must be byte-identical
with ``BALLISTA_FUSION`` ON vs OFF, across the adaptive pass (default
on) and with the shape-bucket ladder on or off.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from ballista_tpu import Int64, Utf8, col, schema

QDIR = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "tpch",
                    "queries")
DEV = os.path.join(os.path.dirname(__file__), "..", "dev")


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from benchmarks.tpch import datagen

    d = str(tmp_path_factory.mktemp("fusion_tpch"))
    datagen.generate(d, scale=0.002, num_parts=2)
    return d


@pytest.fixture(autouse=True)
def _fusion_env(monkeypatch):
    """Tests toggle BALLISTA_FUSION (some via direct os.environ writes
    inside helpers); restore the process default afterwards either
    way."""
    prev = os.environ.get("BALLISTA_FUSION")
    yield
    monkeypatch.undo()
    if prev is None:
        os.environ.pop("BALLISTA_FUSION", None)
    else:
        os.environ["BALLISTA_FUSION"] = prev


def _run_tpch(data_dir, qname, fusion: str):
    from ballista_tpu.client import BallistaContext
    from benchmarks.tpch.schema_def import register_tpch

    os.environ["BALLISTA_FUSION"] = fusion
    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    sql = open(os.path.join(QDIR, f"{qname}.sql")).read()
    df = ctx.sql(sql)
    out = df.collect()
    return out, df._phys


def _assert_byte_identical(a, b, tag):
    assert list(a.columns) == list(b.columns), tag
    assert len(a) == len(b), tag
    for c in a.columns:
        ga, gb = a[c].to_numpy(), b[c].to_numpy()
        assert ga.dtype == gb.dtype, f"{tag}.{c}: {ga.dtype} vs {gb.dtype}"
        if ga.dtype.kind in "fc":  # byte-identical, not merely close
            assert ga.tobytes() == gb.tobytes(), f"{tag}.{c}"
        else:
            np.testing.assert_array_equal(ga, gb, err_msg=f"{tag}.{c}")


def _count_type(phys, cls) -> int:
    n = int(isinstance(phys, cls))
    return n + sum(_count_type(c, cls) for c in phys.children())


# ---------------------------------------------------------------------------
# determinism: fusion ON vs OFF, byte-identical (adaptive pass included
# — it is on by default and q5/q12 exercise its join rules)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname", ["q1", "q5", "q12", "q16"])
def test_determinism_fusion_on_off(tpch_dir, monkeypatch, qname):
    monkeypatch.setenv("BALLISTA_FUSION", "0")
    base, _ = _run_tpch(tpch_dir, qname, "0")
    got, phys = _run_tpch(tpch_dir, qname, "on")
    _assert_byte_identical(base, got, qname)


def test_determinism_buckets_off(tpch_dir, monkeypatch):
    """Fusion must stay byte-identical when the shape-bucket ladder is
    disabled (exact power-of-two capacities)."""
    from ballista_tpu.compile import reconfigure

    monkeypatch.setenv("BALLISTA_SHAPE_BUCKETS", "off")
    reconfigure()
    try:
        base, _ = _run_tpch(tpch_dir, "q1", "0")
        got, _ = _run_tpch(tpch_dir, "q1", "on")
        _assert_byte_identical(base, got, "q1[buckets=off]")
    finally:
        monkeypatch.undo()
        reconfigure()


# ---------------------------------------------------------------------------
# plan shape: fused operators present, escape hatch works, EXPLAIN
# renders fusion groups
# ---------------------------------------------------------------------------


def test_fused_operators_in_plans(tpch_dir):
    from ballista_tpu.physical.fusion import (FusedDistinctCountExec,
                                              FusedStageExec)

    _, p1 = _run_tpch(tpch_dir, "q1", "on")
    assert _count_type(p1, FusedStageExec) >= 1, p1.pretty()
    _, p16 = _run_tpch(tpch_dir, "q16", "on")
    assert _count_type(p16, FusedDistinctCountExec) == 1, p16.pretty()


def test_fusion_escape_hatch(tpch_dir):
    from ballista_tpu.physical.fusion import (FusedDistinctCountExec,
                                              FusedStageExec)

    _, p1 = _run_tpch(tpch_dir, "q1", "0")
    assert _count_type(p1, FusedStageExec) == 0
    _, p16 = _run_tpch(tpch_dir, "q16", "0")
    assert _count_type(p16, FusedDistinctCountExec) == 0


def test_probe_chain_fused_into_join(tpch_dir):
    from ballista_tpu.physical.join import JoinExec

    _, p5 = _run_tpch(tpch_dir, "q5", "on")

    def any_fused_probe(node):
        if isinstance(node, JoinExec) and node.probe_chain:
            return True
        return any(any_fused_probe(c) for c in node.children())

    assert any_fused_probe(p5), p5.pretty()


def test_explain_renders_fusion_groups(tpch_dir, monkeypatch):
    from ballista_tpu.client import BallistaContext
    from benchmarks.tpch.schema_def import register_tpch

    monkeypatch.setenv("BALLISTA_FUSION", "on")
    ctx = BallistaContext.standalone()
    register_tpch(ctx, tpch_dir, "tbl")
    sql = open(os.path.join(QDIR, "q1.sql")).read().rstrip().rstrip(";")
    out = ctx.sql("explain " + sql).collect()
    text = out[out.plan_type == "physical_plan"].plan.iloc[0]
    assert "[fused stage" in text, text
    assert "[fused]" in text, text  # absorbed operators still rendered


def test_explain_analyze_fused_stage_metrics(tpch_dir, monkeypatch):
    """ANALYZE runs the fused plan and the fused stage line carries the
    compile/execute split."""
    from ballista_tpu.client import BallistaContext
    from benchmarks.tpch.schema_def import register_tpch

    monkeypatch.setenv("BALLISTA_FUSION", "on")
    ctx = BallistaContext.standalone()
    register_tpch(ctx, tpch_dir, "tbl")
    sql = open(os.path.join(QDIR, "q1.sql")).read().rstrip().rstrip(";")
    out = ctx.sql("explain analyze " + sql).collect()
    text = out[out.plan_type == "plan_with_metrics"].plan.iloc[0]
    stage_line = next(l for l in text.splitlines() if "[fused stage" in l)
    assert "elapsed_compute" in stage_line, text
    assert "output_rows" in stage_line, text


# ---------------------------------------------------------------------------
# re-plan: fresh operator instances re-fuse onto the same governed
# entries — zero new compiles (the adaptive-execution contract)
# ---------------------------------------------------------------------------


def _compile_requests() -> int:
    from ballista_tpu.compile import compile_stats

    st = compile_stats()
    return int(st["backend_compiles"]) + int(st["persistent_cache_hits"])


def test_replan_of_fused_plan_zero_new_compiles(tpch_dir, monkeypatch):
    from ballista_tpu.client import BallistaContext
    from benchmarks.tpch.schema_def import register_tpch

    monkeypatch.setenv("BALLISTA_FUSION", "on")
    ctx = BallistaContext.standalone()
    register_tpch(ctx, tpch_dir, "tbl")
    sql = open(os.path.join(QDIR, "q1.sql")).read()
    first = ctx.sql(sql).collect()
    # fresh DataFrame -> plan_logical + fuse_plan run again -> ALL-NEW
    # fused operator instances (same value signatures)
    ctx._plan_cache.clear()
    before = _compile_requests()
    second = ctx.sql(sql).collect()
    assert _compile_requests() == before, (
        "re-planned fused query issued new compile requests; fused "
        "signatures must reuse governed entries")
    assert first.equals(second)


# ---------------------------------------------------------------------------
# the distinct-count kernel
# ---------------------------------------------------------------------------


def test_grouped_distinct_count_kernel():
    import jax.numpy as jnp

    from ballista_tpu.kernels.aggregate import grouped_distinct_count

    rng = np.random.RandomState(3)
    n = 512
    g = rng.randint(0, 7, n).astype(np.int64)
    x = rng.randint(0, 23, n).astype(np.int64)
    live = rng.rand(n) > 0.2
    xvalid = rng.rand(n) > 0.3
    res = grouped_distinct_count(
        [jnp.asarray(g)], jnp.asarray(live), jnp.asarray(x), 16,
        distinct_validity=jnp.asarray(xvalid))
    got = {}
    order = np.asarray(res.rep_indices)
    counts = np.asarray(res.aggregates[0])
    valid = np.asarray(res.group_valid)
    for i in range(16):
        if valid[i]:
            got[g[order[i]]] = counts[i]
    exp = {}
    for gv in np.unique(g[live]):
        m = live & (g == gv)
        exp[gv] = len(np.unique(x[m & xvalid]))
    assert got == exp
    assert int(res.num_groups) == len(exp)


def test_distinct_single_partition_drops_dedup():
    """With a single input partition the (g, x) dedup partial is pure
    overhead — the fused stage must absorb the dedup's own scan chain
    instead."""
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.physical.fusion import FusedDistinctCountExec
    from ballista_tpu.physical.aggregate import HashAggregateExec

    os.environ["BALLISTA_FUSION"] = "on"
    ctx = BallistaContext.standalone()
    n = 400
    rng = np.random.RandomState(11)
    ctx.register_memtable("t_dist", schema(
        ("k", Int64), ("v", Int64)), {
        "k": rng.randint(0, 5, n).astype(np.int64),
        "v": rng.randint(0, 50, n).astype(np.int64),
    })
    df = ctx.sql("select k, count(distinct v) as dv from t_dist "
                 "where v > 4 group by k order by k")
    out = df.collect()
    phys = df._phys
    assert _count_type(phys, FusedDistinctCountExec) == 1, phys.pretty()
    # the whole double-agg tower AND the dedup partial are gone
    assert _count_type(phys, HashAggregateExec) == 0, phys.pretty()

    # oracle over the registered arrays
    import pandas as pd

    raw = ctx.sql("select k, v from t_dist").collect()
    k = np.asarray(raw["k"])
    v = np.asarray(raw["v"])
    exp = (pd.DataFrame({"k": k, "v": v}).query("v > 4")
           .groupby("k")["v"].nunique().reset_index()
           .rename(columns={"v": "dv"}).sort_values("k")
           .reset_index(drop=True))
    assert list(out["k"]) == list(exp["k"])
    assert list(out["dv"]) == list(exp["dv"])


# ---------------------------------------------------------------------------
# program-count regression gate (dev/check_jit_sites.py --budget)
# ---------------------------------------------------------------------------


def test_program_budget_gate():
    """q1+q5 with fusion ON must mint no more governed entries than the
    pinned budget, and the fused operators must actually be in the
    plans — fails on silent de-fusion. Subprocess: the gate needs a
    clean process-wide governor."""
    proc = subprocess.run(
        [sys.executable, os.path.join(DEV, "check_jit_sites.py"),
         "--budget"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BALLISTA_METRICS": "0"},
        cwd=os.path.join(os.path.dirname(__file__), ".."),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
