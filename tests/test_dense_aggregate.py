"""The dense grouped aggregate as production runs it (one XLA path), at
the small batches a pytest-only branch used to route elsewhere
(<= 4,096 rows): exact int64 sums against NumPy, and q1 against the
oracle with 1,024-row batches."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu.kernels.aggregate import AggInput, dense_grouped_aggregate

CAPACITIES = [8, 1024, 4096]


def _check(res, gids, live, num_groups, expect):
    """``expect``: per aggregate (op, values | None, validity | None)."""
    gv = np.asarray(res.group_valid)
    reps = np.asarray(res.rep_indices)
    assert int(res.num_groups) == len(set(gids[live].tolist()))
    for g in range(num_groups):
        m = live & (gids == g)
        assert bool(gv[g]) == bool(m.any())
        if m.any():
            assert reps[g] == np.flatnonzero(m)[0]
        for i, (op, v, valid) in enumerate(expect):
            mv = m if valid is None else m & valid
            got = int(res.aggregates[i][g])
            if op == "count":
                assert got == int(mv.sum())
                continue
            assert bool(res.agg_valid[i][g]) == bool(mv.any())
            if mv.any():
                want = {"sum": np.sum, "min": np.min, "max": np.max}[op](
                    v[mv])
                assert got == int(want), (op, g)


def _run(gids, live, num_groups, expect):
    aggs = [AggInput(op, None if v is None else jnp.asarray(v),
                     None if valid is None else jnp.asarray(valid))
            for op, v, valid in expect]
    res = dense_grouped_aggregate(jnp.asarray(gids), jnp.asarray(live),
                                  aggs, num_groups)
    _check(res, gids, live, num_groups, expect)
    return res


@pytest.mark.parametrize("n", CAPACITIES)
def test_exact_signed_values_near_int64_limits(n):
    """Sums stay int64-exact where an f64 accumulator would round:
    signed values as large as the batch allows without overflow (a
    group's sum reaches 2^62 in magnitude)."""
    rng = np.random.default_rng(1)
    G = 6
    gids = rng.integers(0, G, n).astype(np.int32)
    live = rng.random(n) < 0.7
    live[:2] = True
    lim = (1 << 62) // n
    v1 = rng.integers(-lim, lim, n)
    v1[0], v1[1], gids[0], gids[1] = lim, -lim + 1, 0, 1
    v2 = rng.integers(0, 10**7, n)
    _run(gids, live, G, [("sum", v1, None), ("sum", v2, None),
                         ("count", None, None), ("min", v1, None),
                         ("max", v1, None)])


@pytest.mark.parametrize("n", CAPACITIES)
def test_empty_group_and_all_dead_batch(n):
    gids = np.zeros(n, np.int32)
    gids[-1] = 2
    live = np.zeros(n, bool)
    live[[0, -1]] = True
    if n > 2:
        gids[1], live[1] = 0, False
    v = np.arange(5, 5 + n, dtype=np.int64)
    res = _run(gids, live, 4, [("sum", v, None), ("count", None, None)])
    assert [int(x) for x in res.aggregates[0]] == [5, 0, 5 + n - 1, 0]
    assert [int(x) for x in res.aggregates[1]] == [1, 0, 1, 0]
    dead = _run(gids, np.zeros(n, bool), 4,
                [("sum", v, None), ("count", None, None)])
    assert int(dead.num_groups) == 0
    assert not np.asarray(dead.group_valid).any()
    assert [int(x) for x in dead.aggregates[0]] == [0, 0, 0, 0]


@pytest.mark.parametrize("n", CAPACITIES)
def test_256_groups_with_validity_masks(n):
    """G=256 (the dense path's ceiling) with validity-masked sums and
    counts; an all-NULL group reports NULL, not the identity."""
    rng = np.random.default_rng(9)
    G = 256
    gids = rng.integers(0, G, n).astype(np.int32)
    live = rng.random(n) < 0.8
    v1 = rng.integers(-(1 << 49), 1 << 49, n)
    v2 = rng.integers(0, 10**9, n)
    valid1 = rng.random(n) < 0.6
    _run(gids, live, G, [("sum", v1, valid1), ("sum", v2, None),
                         ("count", None, valid1), ("count", None, None),
                         ("min", v2, None)])


def test_q1_with_1024_row_batches_matches_oracle(tmp_path):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.compile import governor
    from benchmarks.tpch import datagen, oracle
    from benchmarks.tpch.schema_def import register_tpch

    d = str(tmp_path / "data")
    datagen.generate(d, scale=0.002, num_parts=4)
    ctx = BallistaContext.standalone()
    register_tpch(ctx, d, "tbl", batch_capacity=1024)
    sql = open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                            "tpch", "queries", "q1.sql")).read()

    def dense_calls():
        return sum(r["calls"] for r in governor().entry_rows()
                   if r["namespace"].startswith("agg.grouped"))

    before = dense_calls()
    got = ctx.sql(sql).collect().reset_index(drop=True)
    tables = oracle.load_tables(d)
    # the aggregate concatenates a partition's 1,024-row scan batches: four
    # partitions of ~3k rows, each one dense call at a 4,096-row capacity
    assert len(tables["lineitem"]) / 4 < 4096
    assert dense_calls() - before >= 4
    exp = oracle.ORACLES["q1"](tables).reset_index(drop=True)
    assert len(got) == len(exp)
    for c in exp.columns:
        g, e = got[c], exp[c]
        if e.dtype.kind in "fc":
            np.testing.assert_allclose(g.astype(float), e.astype(float),
                                       rtol=1e-6, atol=1e-6, err_msg=c)
        else:
            np.testing.assert_array_equal(g.to_numpy(), e.to_numpy(),
                                          err_msg=c)
