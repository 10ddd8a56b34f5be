"""The shuffle write partitions a batch in ONE pass (ISSUE 44).

``Executor._write_shuffled`` computes one destination vector a batch
(``shuffle_dest_program``), reads it and each column once, sorts it
stably on the host and hands every destination a contiguous slice
(``ipc.partition_to_arrow``). Every case here writes real shuffle files
through ``_write_shuffled`` and holds their record batches to the plain
per-destination reference the loop used to be,
``batch_to_arrow(b.with_selection(sel & (pids == q)))``: schema
metadata and row order included, one record batch an (input batch,
destination), and ``device.block`` spans = batches x (1 + columns)."""

import os
import time
import types

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu import col, schema, Decimal, Int64, Utf8
from ballista_tpu.columnar import ColumnBatch, Dictionary, empty_batch
from ballista_tpu.datatypes import FixedSizeList
from ballista_tpu.distributed.dataplane import shuffle_path
from ballista_tpu.distributed.executor import Executor
from ballista_tpu.distributed.types import PartitionId
from ballista_tpu.errors import QueryCancelled
from ballista_tpu.io import ipc
from ballista_tpu.kernels.expr_eval import Evaluator
from ballista_tpu.lifecycle import CancelToken, bind_token
from ballista_tpu.observability import tracing
from ballista_tpu.physical.operators import (compute_partition_ids,
                                             shuffle_dest_program)

SCHEMA = schema(("k", Int64), ("amount", Decimal(2)), ("name", Utf8),
                ("v", FixedSizeList(Int64, 3)), ("n", Int64))
NAMES = Dictionary(sorted(f"name-{i:03d}" for i in range(37)))
PID = PartitionId("job-one-pass", 3, 2)


def make_batch(seed: int, rows: int, capacity: int, live: float = 0.6,
               key=None) -> ColumnBatch:
    """``rows`` rows (every kind of column the writer encodes: int64, an
    int64 decimal, a utf8 dictionary column, a fixed-size list, a column
    with nulls), ``live`` of them selected."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    arrays = {
        "k": (rng.integers(0, 1 << 40, rows) if key is None
              else np.full(rows, key, np.int64)),
        "amount": rng.integers(-10_000, 10_000, rows),
        "name": rng.integers(0, len(NAMES), rows).astype(np.int32),
        "v": rng.integers(0, 99, (rows, 3)),
        "n": rng.integers(0, 5, rows),
    }
    b = ColumnBatch.from_numpy(
        SCHEMA, arrays, {"name": NAMES}, capacity=capacity,
        validity={"n": rng.random(rows) < 0.7,
                  "v": rng.random(rows) < 0.9})
    keep = np.zeros(capacity, np.bool_)
    keep[:rows] = rng.random(rows) < live
    return b.with_selection(jnp.asarray(keep))


class Plan:
    """What ``_write_shuffled`` asks of a stage's plan."""

    def __init__(self, batches, sch=SCHEMA, before_batch=None):
        self.batches, self.sch, self.before_batch = batches, sch, before_batch

    def output_schema(self):
        return self.sch

    def execute(self, partition):
        for i, b in enumerate(self.batches):
            if self.before_batch is not None:
                self.before_batch(i)
            yield b


def executor_at(tmp_path):
    """All ``_write_shuffled`` reads of its executor."""
    return types.SimpleNamespace(config=types.SimpleNamespace(
        work_dir=str(tmp_path)))


def write(tmp_path, plan, hash_exprs, n_out):
    """Run the shuffle write; (stats, blocked reads spanned, the
    ``shuffle.write`` event, the files' record batches a destination)."""
    me = executor_at(tmp_path)
    events = []
    orig = tracing._emit

    def emit(record):
        if record.get("name") == "shuffle.write":
            events.append(record)
        orig(record)

    key = "device.block:ipc.batch_to_arrow"
    before = tracing.span_totals().get(key, {"count": 0})["count"]
    tracing._emit = emit
    try:
        stats = Executor._write_shuffled(me, PID, plan, (hash_exprs, n_out),
                                         time.time())
    finally:
        tracing._emit = orig
    reads = tracing.span_totals()[key]["count"] - before
    files = []
    for q in range(n_out):
        path = shuffle_path(str(tmp_path), PID.job_id, PID.stage_id,
                            PID.partition_id, q)
        with pa.ipc.open_stream(path) as reader:
            files.append(list(reader))
    assert len(events) == 1
    return stats, reads, events[0], files


def reference(batches, hash_exprs, n_out, sch=SCHEMA):
    """The loop ``_write_shuffled`` used to be: a mask a destination."""
    import jax.numpy as jnp

    ev = Evaluator(sch)
    out = [[] for _ in range(n_out)]
    offset = 0
    for b in batches:
        pids = compute_partition_ids(b, hash_exprs, n_out, offset, ev)
        for q in range(n_out):
            out[q].append(ipc.batch_to_arrow(b.with_selection(
                jnp.logical_and(b.selection, pids == q))))
        offset += b.num_rows_host()
    if not batches:
        out = [[ipc.batch_to_arrow(empty_batch(sch))] for _ in range(n_out)]
    return out


def assert_same_files(files, want):
    assert len(files) == len(want)
    for q, (got_q, want_q) in enumerate(zip(files, want)):
        assert len(got_q) == len(want_q), f"destination {q}"
        for i, (got, ref) in enumerate(zip(got_q, want_q)):
            assert got.schema.equals(ref.schema, check_metadata=True), (q, i)
            assert got.num_rows == ref.num_rows, (q, i)
            assert got.equals(ref), f"destination {q}, batch {i}"
            for a, b in zip(got.columns, ref.columns):
                assert a.null_count == b.null_count
                if pa.types.is_dictionary(a.type):
                    assert a.dictionary.equals(b.dictionary)


def three_batches():
    return [make_batch(1, 1000, 1024), make_batch(2, 37, 64),
            make_batch(3, 500, 1024, live=0.1)]


CASES = {
    # hash on one and on two keys (a utf8 key hashes its string), and
    # round-robin, whose offset carries over the batches
    "hash": ([col("k")], three_batches),
    "hash-two-keys-utf8": ([col("name"), col("k")], three_batches),
    "round-robin": ([], three_batches),
    "empty-batch": ([col("k")],
                    lambda: [make_batch(4, 0, 8), make_batch(5, 100, 128)]),
    "all-dead-batch": ([col("k")],
                       lambda: [make_batch(6, 200, 256, live=0.0),
                                make_batch(7, 100, 128)]),
    "all-dead-round-robin": ([], lambda: [make_batch(8, 200, 256, live=0.0),
                                          make_batch(9, 100, 128)]),
    "one-destination": ([col("k")],
                        lambda: [make_batch(10, 300, 512, key=424242)]),
    "no-batch": ([col("k")], lambda: []),
}


@pytest.mark.parametrize("n_out", [1, 3, 17, 300])
@pytest.mark.parametrize("case", sorted(CASES))
def test_files_equal_the_per_destination_reference(tmp_path, case, n_out):
    hash_exprs, make = CASES[case]
    batches = make()
    stats, reads, event, files = write(tmp_path, Plan(batches), hash_exprs,
                                       n_out)
    assert_same_files(files, reference(batches, hash_exprs, n_out))
    # the law: 1 + columns blocking reads a batch, whatever the fan-out
    # (a task that yields nothing converts its one empty batch once)
    assert reads == max(len(batches), 1) * (1 + len(SCHEMA))
    assert event["reads"] == reads
    assert event["slices"] == len(batches) * n_out
    assert event["batches"] == len(batches)
    assert stats["shuffle_write"] == {
        "shuffle_fan_out": n_out, "shuffle_batches": len(batches),
        "shuffle_slices": len(batches) * n_out, "shuffle_reads": reads}
    live = sum(int(np.asarray(b.selection).sum()) for b in batches)
    assert stats["num_rows"] == live == event["rows"]
    assert len(stats["shuffle_partition_bytes"]) == n_out
    assert stats["num_bytes"] == sum(stats["shuffle_partition_bytes"])
    if case == "one-destination" and n_out > 1:
        assert sorted(sum(rb.num_rows for rb in f) for f in files)[:-1] == (
            [0] * (n_out - 1))


@pytest.mark.parametrize("n_out,dtype", [(1, "uint8"), (255, "uint8"),
                                         (256, "uint16"), (65535, "uint16"),
                                         (65536, "uint32")])
def test_destination_vector_is_the_narrowest_type_dead_rows_last(n_out,
                                                                 dtype):
    b = make_batch(11, 200, 256)
    ev = Evaluator(SCHEMA)
    for exprs in ([col("k")], []):
        dest = np.asarray(shuffle_dest_program(SCHEMA, exprs, n_out)(
            b, np.int32(n_out), np.int32(5 % n_out)))
        assert dest.dtype == np.dtype(dtype)
        sel = np.asarray(b.selection)
        pids = np.asarray(compute_partition_ids(b, exprs, n_out, 5, ev))
        assert (dest[~sel] == n_out).all()
        assert (dest[sel] == pids[sel]).all()


def test_one_program_serves_every_fan_out_of_a_width():
    """The fan-out is an operand: the cost feedback moving 8 -> 17 finds
    the entry, and the program, it already has."""
    from ballista_tpu.compile import compile_stats

    sch = schema(("a", Int64), ("b", Int64))
    b = ColumnBatch.from_numpy(sch, {"a": np.arange(50), "b": np.arange(50)},
                               capacity=64)
    f8 = shuffle_dest_program(sch, [col("a")], 8)
    f8(b, np.int32(8), np.int32(0))
    before = compile_stats()
    f17 = shuffle_dest_program(sch, [col("a")], 17)
    d17 = np.asarray(f17(b, np.int32(17), np.int32(0)))
    after = compile_stats()
    assert after["entries_built"] == before["entries_built"]
    assert after["backend_compiles"] == before["backend_compiles"]
    assert d17[:50].max() < 17 and (d17[50:] == 17).all()


def test_chunked_slices_carry_the_same_rows(tmp_path, monkeypatch):
    """A destination's slice above the chunk size is split by
    ``_iter_chunked`` as the reference's record batch would be."""
    monkeypatch.setenv("BALLISTA_SHUFFLE_CHUNK_BYTES", "4096")
    from ballista_tpu.distributed import spill

    assert spill.shuffle_chunk_bytes() == 4096
    batches = [make_batch(12, 4000, 4096, live=0.9)]
    stats, _, _, files = write(tmp_path, Plan(batches), [col("k")], 3)
    want = reference(batches, [col("k")], 3)
    assert stats["num_batches"] > 3
    for got_q, (ref,) in zip(files, want):
        assert len(got_q) > 1
        assert pa.Table.from_batches(got_q).equals(
            pa.Table.from_batches([ref]))
        assert max(rb.nbytes for rb in got_q) <= 4096 * 1.1


def test_a_fired_cancel_stops_the_write_and_leaves_no_file(tmp_path):
    """The token fires while the second batch is on its way: the write
    stops at the next check and every writer's tmp file goes."""
    token = CancelToken()
    plan = Plan(three_batches(),
                before_batch=lambda i: i == 1 and token.cancel("test"))
    me = executor_at(tmp_path)
    with bind_token(token):
        with pytest.raises(QueryCancelled):
            Executor._write_shuffled(me, PID, plan, ([col("k")], 5),
                                     time.time())
    left = [os.path.join(root, f) for root, _, fs in os.walk(tmp_path)
            for f in fs]
    assert left == []


def test_a_cancel_between_chunks_stops_inside_a_batch(tmp_path, monkeypatch):
    """The token is checked a destination and a chunk: one fired as the
    first destination's slice is written stops the batch's other slices."""
    monkeypatch.setenv("BALLISTA_SHUFFLE_CHUNK_BYTES", "4096")
    token = CancelToken()
    wrote = []
    orig = ipc.PartitionWriter.write_arrow

    def write_arrow(self, rb):
        wrote.append(rb.num_rows)
        orig(self, rb)
        token.cancel("test")

    monkeypatch.setattr(ipc.PartitionWriter, "write_arrow", write_arrow)
    me = executor_at(tmp_path)
    with bind_token(token):
        with pytest.raises(QueryCancelled):
            Executor._write_shuffled(
                me, PID, Plan([make_batch(13, 4000, 4096, live=0.9)]),
                ([col("k")], 4), time.time())
    assert len(wrote) == 1  # the second slice met the fired token
    assert [f for _, _, fs in os.walk(tmp_path) for f in fs] == []
