"""An aggregate takes a partition's batches as they are.

``HashAggregateExec`` / ``FusedStageExec`` hand their governed programs a
TUPLE of the partition's batches when there are several that share their
dictionaries (``_partition_input``), and the program lays them end to end
itself (``physical/base.py`` ``gather_batches``): no eager
``jnp.concatenate`` between the scan and the launch. Held here to the
answer over the one concatenated batch, on every grouping path; to the
``agg.inputs`` event (``single`` | ``in_program`` | ``host_concat``, the last
also for the sort path, whose program is dear to compile a shape); to
donation (a one-batch transient input still donates, pinned pieces never);
and to compiling nothing on the second warm execution.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from ballista_tpu import (Date32, Decimal, Int64, Utf8, avg, col, count, lit,
                          max_, min_, schema, sum_)
from ballista_tpu.cache.donation import (donation_stats, is_transient,
                                         mark_transient)
from ballista_tpu.columnar import ColumnBatch, Dictionary
from ballista_tpu.compile import compile_stats
from ballista_tpu.io import MemTableSource
from ballista_tpu.observability.tracing import ring_records, span_totals
from ballista_tpu.physical import base
from ballista_tpu.physical.aggregate import HashAggregateExec
from ballista_tpu.physical.fusion import FusedStageExec, fuse_plan
from ballista_tpu.physical.operators import FilterExec, ScanExec

SCHEMA = schema(("flag", Utf8), ("status", Utf8), ("day", Date32),
                ("qty", Decimal(2)), ("price", Decimal(2)),
                ("disc", Decimal(2)), ("small", Int64), ("sparse", Int64),
                ("maybe", Int64))
ROWS = 5 * 700  # 700 live rows a piece, so every piece has padding
CAPS = {1: [4096], 2: [2048, 2048], 5: [1024, 1024, 1024, 1024, 2048]}
# which pieces carry a validity for "maybe" (the others have None) and
# which lose rows from their selection
WITH_VALIDITY = {0, 3}
WITH_DEAD_ROWS = {1, 4}


def _table():
    rng = np.random.default_rng(40)
    return {
        "flag": rng.integers(0, 3, ROWS).astype(np.int32),
        "status": rng.integers(0, 2, ROWS).astype(np.int32),
        "day": rng.integers(9000, 9400, ROWS).astype(np.int32),
        "qty": rng.integers(100, 5000, ROWS).astype(np.int64),
        "price": rng.integers(10_000, 9_000_000, ROWS).astype(np.int64),
        "disc": rng.integers(0, 11, ROWS).astype(np.int64),
        "small": rng.integers(-20, 40, ROWS).astype(np.int64),
        "sparse": rng.integers(0, 37, ROWS).astype(np.int64) * 1_000_003_007,
        "maybe": rng.integers(-1000, 1000, ROWS).astype(np.int64),
    }


DICTS = {"flag": Dictionary(["A", "N", "R"]), "status": Dictionary(["F", "O"])}
TABLE = _table()
MAYBE_VALID = np.random.default_rng(41).random(ROWS) < 0.8
DEAD = np.random.default_rng(42).random(ROWS) < 0.3


def _by_piece_of_five(marked, flags, lo, hi):
    """``flags`` where a row lies in one of the ``marked`` fifths of the
    table, else True: 1, 2 and 5 batches hold the SAME logical table."""
    fifth = np.minimum(np.arange(lo, hi) // (ROWS // 5), 4)
    return np.where(np.isin(fifth, list(marked)), flags[lo:hi], True)


def _pieces(n):
    """The table as ``n`` batches with uneven capacities: rows split
    evenly; "maybe" has a validity only in the pieces that hold a NULL
    (of five: two have one, three have ``None``); dead rows inside the
    selection of others."""
    caps, per = CAPS[n], ROWS // n
    out = []
    for i, cap in enumerate(caps):
        lo, hi = i * per, (i + 1) * per
        arrays = {k: v[lo:hi] for k, v in TABLE.items()}
        valid = _by_piece_of_five(WITH_VALIDITY, MAYBE_VALID, lo, hi)
        b = ColumnBatch.from_numpy(
            SCHEMA, arrays, DICTS, cap,
            None if valid.all() else {"maybe": valid})
        sel = np.zeros(cap, bool)
        sel[:per] = _by_piece_of_five(WITH_DEAD_ROWS, ~DEAD, lo, hi)
        out.append(b.with_selection(jnp.asarray(sel)))
    return out


def _scan(batches):
    return ScanExec("t", MemTableSource(SCHEMA, [list(batches)]))


Q1_AGGS = [sum_(col("qty")).alias("sum_qty"),
           sum_(col("price") * (lit(1) - col("disc"))).alias("sum_disc"),
           avg(col("qty")).alias("avg_qty"),
           min_(col("maybe")).alias("lo"),
           count().alias("n")]
SCALAR_AGGS = [sum_(col("price") * col("disc")).alias("revenue"),
               min_(col("maybe")).alias("lo"), max_(col("maybe")).alias("hi"),
               count(col("maybe")).alias("n_maybe"), count().alias("n")]


def _q1_fused(batches):
    node = HashAggregateExec(
        "partial", [col("flag"), col("status")], Q1_AGGS,
        FilterExec(col("day") <= lit(9300), _scan(batches)))
    return fuse_plan(node)


def _q6_fused(batches):
    node = HashAggregateExec(
        "partial", [], SCALAR_AGGS,
        FilterExec((col("day") >= lit(9100)) & (col("qty") < lit(2400)),
                   _scan(batches)))
    return fuse_plan(node)


def _plain(group, aggs, cap=1 << 12):
    def make(batches):
        return HashAggregateExec("partial", [col(g) for g in group], aggs,
                                 _scan(batches), group_capacity=cap)
    return make


def _final_dense(batches):
    """A final aggregate over partial states: each piece aggregated on its
    own, the states handed over as the final's partition."""
    states = [next(iter(_plain(["flag"], Q1_AGGS)([b]).execute(0)))
              for b in batches]
    src = MemTableSource(states[0].schema, [states])
    return HashAggregateExec("final", [col("flag")], Q1_AGGS,
                             ScanExec("p", src))


SHAPES = {
    "q1_fused_dense": _q1_fused,
    "q6_fused_scalar": _q6_fused,
    "plain_dense": _plain(["flag", "status"], Q1_AGGS),
    "plain_mixed_ranged": _plain(["flag", "small"], Q1_AGGS),
    # 37 hash-like keys against a group capacity of 8: refused the ranged
    # table, sorted, overflowed, re-run at 64
    "plain_sort_overflow_retry": _plain(["sparse"], Q1_AGGS, cap=8),
    "plain_scalar": _plain([], SCALAR_AGGS),
    "final_dense": _final_dense,
}


def _answer(node) -> pd.DataFrame:
    out = list(node.execute(0))
    assert len(out) == 1
    return out[0].to_pandas()


def _events(since: float, name="agg.inputs"):
    """By time, not by position: a ring already full (another file of
    this worker wrote thousands of records) keeps its length."""
    return [r for r in ring_records(since=since) if r["name"] == name]


def _how_counts():
    t = span_totals()
    return {how: t.get(f"agg.inputs:{how}", {"count": 0})["count"]
            for how in ("single", "in_program", "host_concat")}


@pytest.mark.parametrize("n", [1, 2, 5])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_same_answer_as_over_the_concatenated_batch(shape, n):
    pieces = _pieces(n)
    whole = base.concat_batches(SCHEMA, list(pieces))
    assert whole.capacity == sum(CAPS[n])
    expect = _answer(SHAPES[shape]([whole]))
    before = _how_counts()
    got = _answer(SHAPES[shape](pieces))
    pd.testing.assert_frame_equal(got, expect)
    after = _how_counts()
    # the sort path holds a lax.sort, minutes of compiling a shape: it
    # concatenates on the host after all, so that its program sees sums
    # of rungs as before, not their tuples
    several = ("host_concat" if shape == "plain_sort_overflow_retry"
               else "in_program")
    # the final-over-states shape runs one partial a piece first (single)
    if shape != "final_dense":
        how = "single" if n == 1 else several
        assert after[how] == before[how] + 1
        assert sum(after.values()) == sum(before.values()) + 1
    else:
        assert after["in_program"] == before["in_program"] + (n > 1)
        assert after["host_concat"] == before["host_concat"]
    # and the table is the one it claims to be, whatever the split
    assert len(expect) > 0


def test_sort_path_really_overflowed_and_learned():
    node = SHAPES["plain_sort_overflow_retry"](_pieces(5))
    out = _answer(node)
    assert len(out) == 37 and node.group_capacity == 64
    assert node._ranged_rejected


def test_warm_multi_batch_partition_launches_no_eager_concatenate(monkeypatch):
    pieces = _pieces(5)
    nodes = [_q1_fused(pieces), _q6_fused(pieces),
             SHAPES["plain_mixed_ranged"](pieces)]
    expect = [_answer(node) for node in nodes]  # cold: compiles

    def refuse(*a, **k):
        raise AssertionError("eager jnp.concatenate on the aggregate's path")

    mark = time.time()
    stats = compile_stats()
    before = _how_counts()
    # outside a trace jnp.concatenate is an eager launch; the governed
    # programs are compiled, so a warm call traces nothing and cannot
    # reach the patched function
    monkeypatch.setattr(base.jnp, "concatenate", refuse)
    monkeypatch.setattr(base, "concat_batches", refuse)
    got = [_answer(node) for node in nodes]
    monkeypatch.undo()
    for g, e in zip(got, expect):
        pd.testing.assert_frame_equal(g, e)
    after = _how_counts()
    assert after["in_program"] == before["in_program"] + 3
    assert after["single"] == before["single"]
    events = _events(mark)
    assert [(e["how"], e["batches"], e["capacity"]) for e in events] == \
        [("in_program", 5, sum(CAPS[5]))] * 3
    # the second warm execution compiled nothing and read no cache
    now = compile_stats()
    for key in ("backend_compiles", "persistent_cache_hits",
                "entries_built"):
        assert now[key] == stats[key], key


def test_one_batch_counts_single_and_still_donates_when_transient():
    [piece] = _pieces(1)
    node = _q1_fused([piece])
    _answer(node)  # pinned (not transient): compiles agg.grouped
    mark_transient(piece)
    before, donated = _how_counts(), donation_stats()["donated_buffers"]
    _answer(node)
    after = _how_counts()
    assert after["single"] == before["single"] + 1
    assert after["in_program"] == before["in_program"]
    assert donation_stats()["donated_buffers"] == donated + 1
    assert not is_transient(piece)  # claimed by the donating call


def test_differing_dictionaries_count_host_concat_and_unify():
    s = schema(("k", Utf8), ("v", Int64))
    a = ColumnBatch.from_pydict(s, {"k": ["x", "y", "x"], "v": [1, 2, 3]})
    b = ColumnBatch.from_pydict(s, {"k": ["y", "z"], "v": [10, 20]})
    assert a.column("k").dictionary is not b.column("k").dictionary
    node = HashAggregateExec(
        "partial", [col("k")], [sum_(col("v")).alias("s")],
        ScanExec("t", MemTableSource(s, [[a, b]])))
    before = _how_counts()
    out = _answer(node).sort_values("k").reset_index(drop=True)
    after = _how_counts()
    assert after["host_concat"] == before["host_concat"] + 1
    assert after["in_program"] == before["in_program"]
    assert list(out["k"]) == ["x", "y", "z"]
    assert list(out.iloc[:, 1]) == [4, 12, 20]


def test_pinned_batches_alive_and_unchanged_after_the_call():
    pieces = _pieces(5)
    # transient pieces of a several-batch partition are not donated
    # either: the tuple's programs take no donated argument
    mark_transient(pieces[2])
    kept = [jax.tree.map(np.asarray, p) for p in pieces]
    donated = donation_stats()["donated_buffers"]
    for make in (_q1_fused, _q6_fused, SHAPES["plain_mixed_ranged"],
                 SHAPES["plain_sort_overflow_retry"]):
        _answer(make(pieces))
    assert donation_stats()["donated_buffers"] == donated
    for piece, was in zip(pieces, kept):
        for now, then in zip(jax.tree.leaves(piece), jax.tree.leaves(was)):
            assert not now.is_deleted()
            np.testing.assert_array_equal(np.asarray(now), then)


def test_gathered_shape_is_what_the_program_sees():
    pieces = _pieces(5)
    shape = base.gathered_shape(pieces)
    real = base.gather_batches(pieces)
    assert jax.tree.structure(shape) == jax.tree.structure(real)
    for want, have in zip(jax.tree.leaves(shape), jax.tree.leaves(real)):
        assert (want.shape, want.dtype) == (have.shape, have.dtype)
    assert shape.capacity == sum(CAPS[5])
    assert shape.column("maybe").validity is not None
    assert shape.column("qty").validity is None
    assert isinstance(_q1_fused(pieces), FusedStageExec)
