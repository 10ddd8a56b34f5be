"""kernels/search.py: the sorted vector searched in steps of 128.

``count_below`` must equal ``jnp.searchsorted`` (both sides) element for
element, under jit, for every int64 a join key can be: negative values,
keys that differ only in one 32-bit half (the low half with its top bit
set, where a signed compare of the halves would go wrong), the
``INT64_SENTINEL`` padding behind ``num_live``, runs of one key longer
than a block and than a block of blocks; on builds with no level above
them, one and two; for queries below the first and above the last key;
and for query counts below, above and no multiple of the chunk.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu.kernels import search
from ballista_tpu.kernels.join import INT64_SENTINEL

I64 = np.iinfo(np.int64)
SENTINEL = int(INT64_SENTINEL)
BUILDS = (1, 8, 256, 257, 5_000, 40_001, 1 << 20)
KINDS = ("signed", "low_half", "high_half", "sentinel_tail", "run_128",
         "run_128x128", "one_key")
SIDES = ("left", "right")


def _keys(kind, n):
    rng = np.random.default_rng(n)
    if kind == "signed":  # both signs, every magnitude
        keys = rng.integers(I64.min, I64.max, n, dtype=np.int64)
        keys[: n // 3] >>= 40
    elif kind == "low_half":  # one high half; low halves around bit 31
        keys = (np.int64(-7) << 32) | rng.integers(0, 1 << 32, n,
                                                    dtype=np.int64)
    elif kind == "high_half":  # one low half, its top bit set
        keys = (rng.integers(-(1 << 31), 1 << 31, n, dtype=np.int64)
                << 32) | np.int64(0xF000_0001)
    elif kind == "sentinel_tail":  # dead rows behind num_live
        keys = rng.integers(-1000, 1_000_000, n, dtype=np.int64)
        keys[n - max(1, (2 * n) // 5):] = SENTINEL
    elif kind in ("run_128", "run_128x128"):  # one key, many rows
        run = 300 if kind == "run_128" else 128 * 128 + 300
        keys = rng.integers(-(1 << 40), 1 << 40, n, dtype=np.int64)
        at = max(0, (n - run) // 2)
        keys[at:at + run] = np.sort(keys)[n // 2]
    else:
        keys = np.full(n, 42, dtype=np.int64)
    return np.sort(keys)


def _queries(keys, count=600):
    """Keys of the build, their neighbours on both sides, the ends of
    int64 and values no key has: below the first and above the last."""
    rng = np.random.default_rng(keys.size + 1)
    own = rng.choice(keys, count // 3)
    with np.errstate(over="ignore"):
        around = np.concatenate([own + 1, own - 1, own ^ (1 << 31),
                                 own ^ (1 << 32)])
    ends = np.array([I64.min, I64.min + 1, -1, 0, 1, SENTINEL - 1,
                     SENTINEL, keys[0], keys[-1]], dtype=np.int64)
    wild = rng.integers(I64.min, I64.max, count // 3, dtype=np.int64)
    return np.concatenate([own, around, ends, wild])


@functools.lru_cache(maxsize=None)
def _jitted(side):
    return (jax.jit(lambda v, q: search.count_below(
                search.build_levels(v), q, side)),
            jax.jit(lambda v, q: jnp.searchsorted(v, q, side=side)))


def _check(keys, queries, side):
    ours, theirs = _jitted(side)
    got = ours(jnp.asarray(keys), jnp.asarray(queries))
    assert got.dtype == jnp.int32 and got.shape == queries.shape
    np.testing.assert_array_equal(
        np.asarray(got), np.searchsorted(keys, queries, side=side))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(theirs(jnp.asarray(keys),
                                           jnp.asarray(queries))))


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("n", BUILDS)
def test_count_below_equals_searchsorted(n, kind, side):
    keys = _keys(kind, n)
    _check(keys, _queries(keys), side)


CHUNK = search.QUERY_CHUNK


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("count", (7, CHUNK - 1, CHUNK, CHUNK + 1,
                                   CHUNK + CHUNK // 2, 3 * CHUNK))
def test_count_below_chunks_its_queries(count, side):
    """Above QUERY_CHUNK the queries go a chunk at a time, with a
    remainder where their count is no multiple of it."""
    keys = _keys("sentinel_tail", 40_001)
    rng = np.random.default_rng(count)
    queries = np.where(rng.random(count) < 0.5, rng.choice(keys, count),
                       rng.integers(-5000, 1_100_000, count))
    _check(keys, queries.astype(np.int64), side)


@pytest.mark.parametrize("side", SIDES)
@pytest.mark.parametrize("n", (8, 257, 40_001))
def test_count_below_int32(n, side):
    """compact_perm's running count and the probe's running count of
    matches: one plane, non-decreasing, long runs of one value."""
    rng = np.random.default_rng(n)
    counts = np.cumsum(rng.random(n) < 0.05).astype(np.int32)
    queries = np.arange(-1, counts[-1] + 3, dtype=np.int32)
    _check(counts, queries, side)


def test_the_builds_cross_every_level_count():
    assert [search.depth(n) for n in BUILDS] == [0, 0, 0, 1, 1, 2, 2]
    assert search.depth(1 << 21) == 2 and search.depth(1 << 24) == 3
    # a run longer than a block of blocks spans whole rows of a level
    assert 128 * 128 + 300 < BUILDS[-1]


def test_other_dtypes_are_refused():
    with pytest.raises(TypeError):
        search.build_levels(jnp.zeros(4, jnp.float32))
