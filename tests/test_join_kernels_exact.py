"""The probes of kernels/join.py against a NumPy nested-loop join.

``probe_expand``, ``probe_counts`` and the sorted branch of
``probe_unique`` search the sorted build keys in steps of 128
(kernels/search.py). Every index they return must be the one the
nested loop gives: the pairs, their order (probe row by probe row, a
row's matches in build-row order), the truncation at the output
capacity and the returned total, on seeded many-to-many data with dead
probe rows, dead build rows, no match at all, and a total over the
capacity; on builds with no level above them, one and two.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu.kernels import join

SCENARIOS = ("many_to_many", "dead_probe_rows", "dead_build_rows",
             "no_match", "over_capacity", "all_build_dead",
             "negative_and_packed_keys")
SHAPES = ((40, 64), (700, 512), (40_000, 2048))  # build rows, probe rows


def _data(scenario, nb, npr):
    rng = np.random.default_rng(nb * 31 + len(scenario))
    domain = max(4, nb // 3)  # ~3 build rows a key
    bkeys = rng.integers(0, domain, nb).astype(np.int64)
    pkeys = rng.integers(0, domain + domain // 4, npr).astype(np.int64)
    blive = np.ones(nb, np.bool_)
    plive = np.ones(npr, np.bool_)
    if scenario == "dead_probe_rows":
        plive = rng.random(npr) < 0.4
    elif scenario == "dead_build_rows":
        blive = rng.random(nb) < 0.5
    elif scenario == "no_match":
        pkeys += domain
    elif scenario == "all_build_dead":
        blive[:] = False
    elif scenario == "negative_and_packed_keys":
        # two int32 packed into one key, negatives included: halves
        # that differ in one plane only
        pack = lambda k: ((k % 7 - 3) << 32) | ((k * 0x9E3779B1)  # noqa: E731
                                                & 0xFFFF_FFFF)
        bkeys, pkeys = pack(bkeys), pack(pkeys)
        plive = rng.random(npr) < 0.8
        blive = rng.random(nb) < 0.8
    return bkeys, blive, pkeys, plive


def _nested_loop(bkeys, blive, pkeys, plive):
    pairs = []
    for i in np.flatnonzero(plive):
        for j in np.flatnonzero(blive & (bkeys == pkeys[i])):
            pairs.append((i, j))
    return np.asarray(pairs, dtype=np.int64).reshape(-1, 2)


@functools.lru_cache(maxsize=None)
def _expand(capacity):
    def run(bkeys, blive, pkeys, plive):
        table = join.build_lookup(bkeys, blive)
        return join.probe_expand(table, pkeys, plive, capacity)

    return jax.jit(run)


@jax.jit
def _counts(bkeys, blive, pkeys):
    return join.probe_counts(join.build_lookup(bkeys, blive), pkeys)


@jax.jit
def _unique(bkeys, blive, pkeys, plive):
    return join.probe_unique(join.build_lookup(bkeys, blive), pkeys, plive)


@pytest.mark.parametrize("nb,npr", SHAPES)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_probe_expand_equals_nested_loop(scenario, nb, npr):
    bkeys, blive, pkeys, plive = _data(scenario, nb, npr)
    want = _nested_loop(bkeys, blive, pkeys, plive)
    capacity = 4 * npr
    if scenario == "over_capacity":
        capacity = max(8, len(want) // 2)
        assert len(want) > capacity
    prows, brows, olive, total = jax.device_get(_expand(capacity)(
        *map(jnp.asarray, (bkeys, blive, pkeys, plive))))
    assert total == len(want)  # the whole total, truncated or not
    kept = min(len(want), capacity)
    np.testing.assert_array_equal(olive, np.arange(capacity) < kept)
    np.testing.assert_array_equal(prows[:kept], want[:kept, 0])
    np.testing.assert_array_equal(brows[:kept], want[:kept, 1])
    assert not prows[kept:].any() and not brows[kept:].any()


@pytest.mark.parametrize("nb,npr", SHAPES)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_probe_counts_equals_nested_loop(scenario, nb, npr):
    """Every probe row's count, dead probe rows too (the left join masks
    them itself)."""
    bkeys, blive, pkeys, _ = _data(scenario, nb, npr)
    got = np.asarray(_counts(*map(jnp.asarray, (bkeys, blive, pkeys))))
    want = np.array([np.count_nonzero(blive & (bkeys == k)) for k in pkeys])
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("nb,npr", SHAPES)
@pytest.mark.parametrize("scenario", SCENARIOS)
def test_sorted_probe_unique_equals_lookup(scenario, nb, npr):
    """The unique probe too wide for the dense table: build keys made
    unique (a row's key spread by its row number) and sparse."""
    bkeys, blive, pkeys, plive = _data(scenario, nb, npr)
    spread = lambda k, salt: k * 1_000_003 + salt % 5  # noqa: E731
    bkeys = spread(bkeys, 0) + np.arange(nb) * 8  # unique: steps of 8
    pick = np.random.default_rng(nb).integers(0, nb, npr)
    pkeys = np.where(np.arange(npr) % 3 == 0, spread(pkeys, 1),
                     bkeys[pick])
    if scenario == "no_match":
        pkeys = pkeys + 1  # between the keys
    assert len(np.unique(bkeys)) == nb
    rows, matched = jax.device_get(_unique(
        *map(jnp.asarray, (bkeys, blive, pkeys, plive))))
    where = {int(k): j for j, k in enumerate(bkeys) if blive[j]}
    want = np.array([where.get(int(k), -1) if plive[i] else -1
                     for i, k in enumerate(pkeys)])
    np.testing.assert_array_equal(matched, want >= 0)
    np.testing.assert_array_equal(rows, np.maximum(want, 0))
    if scenario not in ("no_match", "all_build_dead"):
        assert matched.any()


def test_the_shapes_cross_every_level_count():
    from ballista_tpu.kernels.search import depth

    assert [depth(nb) for nb, _ in SHAPES] == [0, 1, 2]


@pytest.mark.parametrize("build,searches", [
    ("duplicates", 3),  # the expanding probe: one launch a probe batch
    ("unique_sparse", 3),  # unique, too wide for the dense table
    ("unique_dense", 0),  # the dense table is one gather, no search
])
def test_join_search_counts_the_probes_that_search(build, searches):
    """``join.search`` (physical/join.py): one event a probe batch that
    goes through the stepped search, with the shapes it ran at."""
    from ballista_tpu import Int64, schema
    from ballista_tpu.columnar import ColumnBatch
    from ballista_tpu.io import MemTableSource
    from ballista_tpu.kernels.search import depth
    from ballista_tpu.observability import tracing
    from ballista_tpu.physical.join import JoinExec
    from ballista_tpu.physical.operators import ScanExec

    cap, keys = 1024, 3000
    k = np.arange(keys)
    if build == "duplicates":
        k = np.concatenate([k, k[::3]])
    elif build == "unique_sparse":
        k = k * 1_000_003
    bs = schema(("bk", Int64), ("w", Int64))
    ps = schema(("pk", Int64), ("v", Int64))
    rng = np.random.default_rng(36)
    probe = [ColumnBatch.from_numpy(
        # keys the build holds once: no total passes the output capacity,
        # so no batch is launched a second time
        ps, {"pk": rng.choice(k[1:keys:3], cap), "v": np.arange(cap)},
        capacity=cap) for _ in range(3)]
    join = JoinExec(
        ScanExec("b", MemTableSource.from_pydict(
            bs, {"bk": k, "w": np.arange(len(k))})),
        ScanExec("p", MemTableSource(ps, [probe])), [("bk", "pk")], "inner")
    before = tracing.span_totals().get("join.search", {"count": 0})["count"]
    started = time.time()
    rows = sum(int(b.num_rows) for b in join.execute(0))
    assert rows == 3 * cap
    after = tracing.span_totals().get("join.search", {"count": 0})["count"]
    assert after - before == searches
    mine = [r for r in tracing.ring_records(since=started)
            if r["name"] == "join.search"]
    for rec in mine:
        assert rec["probes"] == cap and rec["build"] >= len(k)
        assert rec["levels"] == depth(rec["build"])
    assert len(mine) == searches or not tracing.ring_records()
