"""A shuffle reader assembles a group in ONE pass (ISSUE 45): the
producers' files decoded to per-record-batch views
(``ipc.read_partition_pieces``), every piece placed once into the group's
padded batch buffers and all of them uploaded in one ``jax.device_put``
(``ipc.batches_from_pieces``).

Held here, over groups of 1, 3, 4 and 24 pieces and a column of every kind
a shuffle file carries: the live rows of the reader's batches, in producer
order then row order, equal a reference built piece by piece from
``read_partition_arrays``; every capacity is a ladder rung no larger than
``DEFAULT_BATCH_CAPACITY``; the ``shuffle.read`` span's ``batches`` and
``uploads`` follow the law; the socket path gives the same batches as the
local one; and a cancel fired mid-group stops the decode."""

import math
import time

import numpy as np
import jax.numpy as jnp
import pyarrow as pa
import pytest

from ballista_tpu.columnar import (
    DEFAULT_BATCH_CAPACITY, Column, ColumnBatch, Dictionary)
from ballista_tpu.compile import bucket_capacity
from ballista_tpu.datatypes import (
    Boolean, Date32, Decimal, Field, FixedSizeList, Int64, Schema, Utf8)
from ballista_tpu.distributed import dataplane
from ballista_tpu.distributed.types import PartitionLocation
from ballista_tpu.errors import QueryCancelled
from ballista_tpu.io import ipc
from ballista_tpu.lifecycle import CancelToken, bind_token
from ballista_tpu.observability import tracing
from ballista_tpu.physical.shuffle import ShuffleReaderExec

JOB, STAGE, OUT = "job1pass", 3, 2
PIECES = (1, 3, 4, 24)
# a piece's rows: uneven, and several record batches a file at CHUNK bytes
ROWS, CHUNK = (700, 1, 1301, 64, 2048), 4096
WORDS = ["ash", "birch", "cedar", "elm", "fir", "hazel", "larch", "oak",
         "pine", "rowan", "teak", "yew"]


def _values(kind, rng, n, piece):
    """(physical values, validity or None, dictionary or None) of one
    piece's column."""
    if kind in ("int64", "nulls_some", "over"):
        vals = rng.integers(-(1 << 60), 1 << 60, n).astype(np.int64)
        # nulls in the odd pieces only: the even ones write no mask
        valid = (rng.random(n) > 0.3) if (
            kind == "nulls_some" and piece % 2 and n) else None
        return vals, valid, None
    if kind == "decimal":
        return rng.integers(-10**12, 10**12, n).astype(np.int64), None, None
    if kind == "date32":
        return rng.integers(0, 20000, n).astype(np.int32), None, None
    if kind == "boolean":
        return rng.random(n) > 0.5, None, None
    if kind == "utf8":
        # a dictionary of its own a piece: another subset, so equal
        # strings carry different codes in different files
        mine = sorted(rng.choice(WORDS, size=3 + piece % 5, replace=False))
        return (rng.integers(0, len(mine), n).astype(np.int32), None,
                Dictionary(np.asarray(mine, dtype=object)))
    assert kind == "list"
    return (rng.integers(-99, 99, (n, 2)).astype(np.int64),
            (rng.random(n) > 0.25) if n else None, None)


DTYPES = {"int64": Int64, "decimal": Decimal(2), "date32": Date32,
          "boolean": Boolean, "utf8": Utf8, "nulls_some": Int64,
          "list": FixedSizeList(Int64, 2), "over": Int64}
# the schema cases: (columns, rows of piece i)
CASES = {
    "int64": (("int64",), None),
    "decimal": (("decimal", "int64"), None),
    "date32": (("date32",), None),
    "boolean": (("boolean", "int64"), None),
    "utf8_dicts_differ": (("utf8", "int64"), None),
    "nulls_some_pieces": (("nulls_some", "decimal"), None),
    "list_validity": (("list", "int64"), None),
    "empty_piece": (("int64", "utf8", "nulls_some", "list"),
                    lambda i: 0 if i % 3 == 1 else ROWS[i % len(ROWS)]),
    "only_empty": (("int64", "utf8", "list"), lambda i: 0),
    "over_capacity": (("over",), "over"),
}


def _rows_of(case, pieces, i):
    rows = CASES[case][1]
    if rows == "over":
        # the group a little over one batch, a piece cut by the boundary
        return (DEFAULT_BATCH_CAPACITY + 4321) // pieces + (i == 0) * (
            (DEFAULT_BATCH_CAPACITY + 4321) % pieces)
    return ROWS[i % len(ROWS)] if rows is None else rows(i)


def _write_group(work, case, pieces):
    """The group's files under ``work`` as a producer task writes them;
    returns (schema, locations without an address)."""
    kinds = CASES[case][0]
    schema = Schema([Field(f"c{j}_{k}", DTYPES[k])
                     for j, k in enumerate(kinds)])
    rng = np.random.default_rng(45 + pieces)
    locs = []
    for i in range(pieces):
        n = _rows_of(case, pieces, i)
        cols = []
        for k in kinds:
            vals, valid, dictionary = _values(k, rng, n, i)
            cols.append(Column(
                jnp.asarray(vals), DTYPES[k],
                None if valid is None else jnp.asarray(valid), dictionary))
        batch = ColumnBatch(schema, cols, jnp.ones(n, dtype=bool),
                            jnp.asarray(np.int32(n)))
        path = dataplane.shuffle_path(str(work), JOB, STAGE, i, OUT)
        w = ipc.PartitionWriter(path, chunk_bytes=CHUNK,
                                compute_column_stats=False)
        if n:
            w.write_batch(batch)
        else:
            # an empty piece is a file with a schema and no row
            w.write_arrow(ipc.batch_to_arrow(batch))
        w.close()
        locs.append(PartitionLocation(JOB, STAGE, i, "e0", "", 0, path,
                                      {"num_rows": n}, OUT))
    return schema, locs


def _logical(values, nulls, dictionary):
    """A column's rows as Python-comparable values, ``None`` for a null."""
    if dictionary is not None:
        words = getattr(dictionary, "values", dictionary)
        values = np.asarray([str(words[c]) for c in values], dtype=object)
    out = [v.tolist() if isinstance(v, np.ndarray) else
           (v.item() if hasattr(v, "item") else v) for v in values]
    return [None if gone else v for v, gone in zip(out, nulls)]


def _reference(schema, locs):
    """Piece by piece through ``read_partition_arrays``, laid end to end."""
    want = {f.name: [] for f in schema.fields}
    for loc in locs:
        _, arrays, nulls, dicts, _ = ipc.read_partition_arrays(loc.path)
        for f in schema.fields:
            want[f.name] += _logical(arrays[f.name], nulls[f.name],
                                     dicts.get(f.name))
    return want


def _live_rows(schema, batches):
    got = {f.name: [] for f in schema.fields}
    for b in batches:
        n = int(b.num_rows)
        sel = np.asarray(b.selection)
        assert sel[:n].all() and not sel[n:].any()
        for f in schema.fields:
            col = b.column(f.name)
            vals = np.asarray(col.values)[:n]
            assert vals.dtype == f.dtype.device_dtype()
            nulls = (np.zeros(n, dtype=bool) if col.validity is None
                     else ~np.asarray(col.validity)[:n])
            got[f.name] += _logical(vals, nulls, col.dictionary)
    return got


def _read(schema, locs):
    """(batches, the ``shuffle.read`` record, the metrics row)."""
    started = time.time()
    reader = ShuffleReaderExec(locs, schema)
    batches = list(reader.execute(OUT))
    (record,) = [r for r in tracing.ring_records(since=started)
                 if r.get("name") == "shuffle.read"]
    return batches, record, reader.metrics().values()


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    work = tmp_path_factory.mktemp("work")
    srv = dataplane.start_data_plane("localhost", 0, str(work))
    yield work, srv.port
    srv.close()


@pytest.mark.parametrize("pieces", PIECES)
@pytest.mark.parametrize("case", list(CASES))
def test_group_in_one_pass(server, monkeypatch, case, pieces):
    work, port = server
    schema, locs = _write_group(work, case, pieces)
    want = _reference(schema, locs)
    rows = len(next(iter(want.values())))
    assert rows == sum(l.stats["num_rows"] for l in locs)

    batches, record, metrics = _read(schema, locs)
    assert _live_rows(schema, batches) == want

    # the law: ceil(rows / DEFAULT_BATCH_CAPACITY) batches (one empty
    # batch for a group whose pieces hold no row, so the schema and the
    # dictionaries still travel), full ones at the constant and the last
    # at a ladder rung
    n_batches = max(1, math.ceil(rows / DEFAULT_BATCH_CAPACITY))
    assert len(batches) == record["batches"] == n_batches
    sizes = [int(b.num_rows) for b in batches]
    assert sizes[:-1] == [DEFAULT_BATCH_CAPACITY] * (n_batches - 1)
    # (the rest of a group of several batches takes at least half a
    # batch, so what a join lays end to end has few sizes to compile for)
    least = DEFAULT_BATCH_CAPACITY // 2 if n_batches > 1 else 1
    for b, n in zip(batches, sizes):
        assert b.capacity == bucket_capacity(max(n, least)) \
            <= DEFAULT_BATCH_CAPACITY
    # every array of the group handed to the device in one call: a
    # batch's columns, the validities it has, its selection and row count
    arrays = sum(len(schema.fields) + 2
                 + sum(c.validity is not None for c in b.columns)
                 for b in batches)
    assert record["uploads"] == metrics["uploads"] == arrays
    assert (record["pieces"], record["local"], record["rows"]) == \
        (pieces, pieces, rows)
    assert record["capacity"] == sum(b.capacity for b in batches)
    # one dictionary a utf8 column over the whole group
    for f in schema.fields:
        if f.dtype.kind == "utf8":
            assert len({id(b.column(f.name).dictionary)
                        for b in batches}) == 1

    # the socket path: the same batches, array for array
    monkeypatch.setattr(ShuffleReaderExec, "FORCE_REMOTE", True)
    remote_locs = [PartitionLocation(l.job_id, l.stage_id, l.partition_id,
                                     l.executor_id, "localhost", port,
                                     l.path, l.stats, l.shuffle_output)
                   for l in locs]
    remote, remote_record, _ = _read(schema, remote_locs)
    assert remote_record["local"] == 0
    assert (remote_record["batches"], remote_record["uploads"]) == \
        (record["batches"], record["uploads"])
    assert len(remote) == len(batches)
    for a, b in zip(remote, batches):
        assert int(a.num_rows) == int(b.num_rows)
        np.testing.assert_array_equal(np.asarray(a.selection),
                                      np.asarray(b.selection))
        for ca, cb in zip(a.columns, b.columns):
            np.testing.assert_array_equal(np.asarray(ca.values),
                                          np.asarray(cb.values))
            assert (ca.validity is None) == (cb.validity is None)
            if ca.validity is not None:
                np.testing.assert_array_equal(np.asarray(ca.validity),
                                              np.asarray(cb.validity))
            if cb.dictionary is not None:
                assert list(ca.dictionary.values) == \
                    list(cb.dictionary.values)


@pytest.mark.parametrize("pieces", PIECES)
def test_pieces_cut_at_the_batch_boundary(server, monkeypatch, pieces):
    """With the constant lowered, every kind of column is cut where a
    batch ends, inside a piece and inside a record batch: the rows still
    equal the reference and the capacities the law."""
    work, _ = server
    cap = 1024
    monkeypatch.setattr(ipc, "DEFAULT_BATCH_CAPACITY", cap)
    schema, locs = _write_group(work, "empty_piece", pieces)
    want = _reference(schema, locs)
    rows = len(next(iter(want.values())))
    batches, record, _ = _read(schema, locs)
    assert _live_rows(schema, batches) == want
    assert len(batches) == record["batches"] == max(1, math.ceil(rows / cap))
    assert [b.capacity for b in batches[:-1]] == [cap] * (len(batches) - 1)
    assert batches[-1].capacity <= cap


@pytest.mark.parametrize("pieces", PIECES)
def test_views_not_copies_where_a_record_batch_has_no_null(server, pieces):
    """The decode makes a mask only where Arrow's ``null_count`` says a
    record batch's column has a null, and no array a file."""
    work, _ = server
    schema, locs = _write_group(work, "nulls_some_pieces", pieces)
    for i, loc in enumerate(locs):
        fp = ipc.read_partition_pieces(loc.path)
        assert fp.rows == loc.stats["num_rows"]
        nulls_col, plain_col = (f.name for f in schema.fields)
        # several record batches a file of any size
        assert len(fp.values[plain_col]) > 1 or fp.rows < 700
        assert all(nm is None for nm in fp.nulls[plain_col])
        # views of the file's own buffers, made by ``np.frombuffer`` (which
        # holds the GIL; ``to_numpy`` leaves it a column a record batch)
        assert all(not p.flags.owndata for p in fp.values[plain_col])
        assert all(isinstance(p.base, pa.Buffer)
                   for p in fp.values[plain_col])
        masked = [nm is not None for nm in fp.nulls[nulls_col]]
        # (an odd piece of a row or two may draw no null)
        assert any(masked) == bool(i % 2) or (i % 2 and fp.rows < 64)
        # read_partition_arrays keeps its result: whole arrays and masks
        _, arrays, nulls, _, _ = ipc.read_partition_arrays(loc.path)
        assert len(arrays[plain_col]) == len(nulls[plain_col]) == fp.rows
        assert int(nulls[nulls_col].sum()) == sum(
            int(nm.sum()) for nm in fp.nulls[nulls_col] if nm is not None)


@pytest.mark.parametrize("after", [0, 1, 5])
def test_a_cancel_fired_mid_group_stops_the_decode(server, monkeypatch,
                                                   after):
    """The token fires once ``after`` record batches of the group are
    decoded: the read raises at the next one and places nothing."""
    work, _ = server
    schema, locs = _write_group(work, "int64", 4)
    token = CancelToken()
    seen = []
    batch_iter = ipc._batch_iter

    def counting(reader, in_memory=False):
        for columns in batch_iter(reader, in_memory):
            if len(seen) == after:
                token.cancel("test")
            seen.append(len(columns[0]))
            yield columns

    monkeypatch.setattr(ipc, "_batch_iter", counting)
    monkeypatch.setattr(
        ipc, "batches_from_pieces",
        lambda *a, **k: pytest.fail("placed a cancelled group"))
    # in-thread decode, so the count of decoded record batches is exact
    monkeypatch.setattr("ballista_tpu.ingest.parallel_map",
                        lambda fn, items: [fn(i) for i in items])
    with bind_token(token):
        with pytest.raises(QueryCancelled):
            list(ShuffleReaderExec(locs, schema).execute(OUT))
    assert len(seen) == after + 1
