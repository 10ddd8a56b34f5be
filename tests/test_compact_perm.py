"""compact_perm: the k-th live row by searching the running count.

It must equal ``jnp.nonzero(sel, size=size, fill_value=0)[0]`` element
for element, under jit, on every shape and selection the engine can
hand it: capacities that need no level above the running count, one,
two and three; capacities that are no multiple of a block or of a row
of the count; sizes below and above the chunk the queries go in, and no
multiple of it. maybe_compact on top of it keeps validity and
dictionaries and counts each compaction.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu import Int64, Utf8, schema
from ballista_tpu.columnar import ColumnBatch, Dictionary
from ballista_tpu.compile import bucket_capacity
from ballista_tpu.observability import tracing
from ballista_tpu.physical import base
from ballista_tpu.physical.base import compact_perm, maybe_compact

CAPACITIES = (8, 1024, 65536, 1 << 20)
ODD_CAPACITIES = (100, 1000, 5000, 40001)
SELECTIONS = ("all_dead", "all_live", "first_only", "last_only",
              "random_1pct", "random_50pct", "live_tail")


def _sizes(cap):
    """From 8 to the capacity; past 2**16 the queries go in chunks."""
    return sorted({8, *(max(8, cap // d) for d in (64, 16, 8, 4, 2, 1))})


def _selection(kind, cap):
    sel = np.zeros(cap, dtype=np.bool_)
    rng = np.random.default_rng(cap)
    if kind == "all_live":
        sel[:] = True
    elif kind == "first_only":
        sel[0] = True
    elif kind == "last_only":
        sel[-1] = True
    elif kind == "random_1pct":
        sel[:] = rng.random(cap) < 0.01
    elif kind == "random_50pct":
        sel[:] = rng.random(cap) < 0.5
    elif kind == "live_tail":  # every survivor behind a long dead run
        sel[cap - max(1, cap // 8):] = True
    return sel


@functools.lru_cache(maxsize=None)
def _jitted(size):
    return (jax.jit(functools.partial(compact_perm, size=size)),
            jax.jit(lambda s: jnp.nonzero(s, size=size, fill_value=0)[0]))


CASES = [(cap, size, kind) for cap in CAPACITIES for size in _sizes(cap)
         for kind in SELECTIONS]


def _check(cap, size, kind):
    sel_np = _selection(kind, cap)
    sel = jnp.asarray(sel_np)
    ours, theirs = _jitted(size)
    got = ours(sel)
    assert got.dtype == jnp.int32 and got.shape == (size,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(theirs(sel)))
    # and against the definition: the first `size` live rows, then 0
    live = np.flatnonzero(sel_np)[:size]
    want = np.zeros(size, dtype=np.int32)
    want[:len(live)] = live
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("cap,size,kind", CASES)
def test_compact_perm_equals_nonzero(cap, size, kind):
    _check(cap, size, kind)


ODD_CASES = [(cap, size, kind) for cap in ODD_CAPACITIES
             for size in (8, cap // 3, cap) for kind in SELECTIONS]


@pytest.mark.parametrize("cap,size,kind", ODD_CASES)
def test_compact_perm_on_capacities_off_the_ladder(cap, size, kind):
    """The mesh path compacts whatever capacity a concatenation has."""
    _check(cap, size, kind)


CHUNK = base._QUERY_CHUNK


@pytest.mark.parametrize("size", (CHUNK, CHUNK + 1, CHUNK + CHUNK // 2,
                                  3 * CHUNK))
def test_compact_perm_chunks_its_queries(size):
    """Above _QUERY_CHUNK the queries go a chunk at a time, with a
    remainder where the size is no multiple of it."""
    _check(4 * CHUNK, size, "random_50pct")


def test_the_shapes_cross_every_level_count():
    def levels(cap):
        n, above = cap, 0
        while n > base._TOP:
            n, above = -(-n // base._BLOCK), above + 1
        return above

    assert [levels(c) for c in CAPACITIES] == [0, 1, 2, 2]
    assert levels(1 << 22) == 2 and levels(1 << 24) == 3
    assert any(c % base._BLOCK for c in ODD_CAPACITIES)
    assert any(c > base._COUNT_ROW and c % base._COUNT_ROW
               for c in ODD_CAPACITIES)


def test_compact_perm_three_levels():
    _check(1 << 24, 4096, "random_1pct")


def _sparse_batch(cap, live_rows):
    s = schema(("k", Int64), ("name", Utf8), ("v", Int64))
    words, codes = Dictionary.encode(["w%d" % (i % 7) for i in range(cap)])
    valid = (np.arange(cap) % 3) != 0
    b = ColumnBatch.from_numpy(
        s, {"k": np.arange(cap), "name": codes, "v": np.arange(cap) * 10},
        dictionaries={"name": words}, capacity=cap, validity={"v": valid})
    sel = np.zeros(cap, dtype=np.bool_)
    sel[live_rows] = True
    return b.with_selection(jnp.asarray(sel)), words, codes, valid


def test_maybe_compact_keeps_validity_and_dictionary():
    cap = 4096
    live_rows = np.sort(np.random.default_rng(29).choice(cap, 37, False))
    batch, words, codes, valid = _sparse_batch(cap, live_rows)
    before = tracing.span_totals()
    out = maybe_compact(batch, known_rows=len(live_rows))
    after = tracing.span_totals()

    assert out.capacity == bucket_capacity(37) < cap
    assert int(out.num_rows) == 37
    n = 37
    np.testing.assert_array_equal(
        np.asarray(out.selection), np.arange(out.capacity) < n)
    # survivors in their input order, every column gathered alike
    np.testing.assert_array_equal(
        np.asarray(out.column("k").values)[:n], live_rows)
    np.testing.assert_array_equal(
        np.asarray(out.column("v").values)[:n], live_rows * 10)
    np.testing.assert_array_equal(
        np.asarray(out.column("v").validity)[:n], valid[live_rows])
    assert out.column("k").validity is None
    np.testing.assert_array_equal(
        np.asarray(out.column("name").values)[:n], codes[live_rows])
    assert out.column("name").dictionary is words
    got = out.to_pydict()
    assert list(got["name"]) == ["w%d" % (i % 7) for i in live_rows]

    def count(totals, name):
        return totals.get(name, {"count": 0})["count"]

    assert count(after, "compact.search") == \
        count(before, "compact.search") + 1


def test_maybe_compact_leaves_a_full_batch_alone_and_counts_nothing():
    cap = 4096
    batch, *_ = _sparse_batch(cap, np.arange(cap // 2))
    before = tracing.span_totals()
    assert maybe_compact(batch, known_rows=cap // 2) is batch
    assert tracing.span_totals().get("compact.search") == \
        before.get("compact.search")


@pytest.mark.parametrize("cap_out", (64, 256, 1024, 4096))
def test_mesh_compact_to_any_capacity(cap_out):
    """_compact_impl may ask for every row of the capacity or pad beyond
    it; both keep the live rows first, in order."""
    from ballista_tpu.physical.mesh_input import _compact_impl

    cap = 1024
    live_rows = np.arange(3, cap, 17)[:min(cap_out, 61)]
    batch, _, codes, valid = _sparse_batch(cap, live_rows)
    out = jax.jit(functools.partial(_compact_impl, cap=cap_out))(batch)
    n = len(live_rows)
    assert out.capacity == cap_out and int(out.num_rows) == n
    np.testing.assert_array_equal(
        np.asarray(out.column("k").values)[:n], live_rows)
    np.testing.assert_array_equal(
        np.asarray(out.column("name").values)[:n], codes[live_rows])
    np.testing.assert_array_equal(
        np.asarray(out.column("v").validity)[:n], valid[live_rows])
    assert not np.asarray(out.column("v").validity)[n:].any()
