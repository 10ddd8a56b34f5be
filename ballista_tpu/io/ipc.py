"""Columnar IPC persistence for shuffle partitions and result fetch.

Equivalent of the reference's Arrow-IPC shuffle materialization
(reference: rust/core/src/utils.rs:49-84 ``write_stream_to_disk`` +
executor FetchPartition serving at rust/executor/src/flight_service.rs:
193-228). Files are Arrow IPC (pyarrow); the engine's physical column
representations map to Arrow as:

- decimal(s)  -> int64 + field metadata ballista.kind=decimal/scale
- date32      -> int32 + metadata
- utf8        -> Arrow dictionary<int32, utf8> (codes survive verbatim)

Rows are COMPACTED to the live selection before writing, so shuffle files
carry no padding. Readers get physical arrays back plus per-file
dictionaries; ``unify_dictionaries`` merges multiple producers' codes into
one table-wide dictionary via searchsorted remapping (no per-row decode).

Streaming layout (docs/shuffle.md): writers emit Arrow IPC **stream**
format with record batches bounded to ``BALLISTA_SHUFFLE_CHUNK_BYTES``
(:class:`PartitionWriter`), so the data plane can serve and readers can
decode partitions chunk-by-chunk without whole-partition buffering.
Readers sniff the format (``ARROW1`` magic = legacy file format) so
both layouts stay readable.
"""

from __future__ import annotations

import io as _io
import os
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

import numpy as np

from ..columnar import (
    DEFAULT_BATCH_CAPACITY, Column, ColumnBatch, Dictionary)
from ..compile import bucket_capacity
from ..datatypes import Field, Schema
from ..errors import IoError

ARROW_FILE_MAGIC = b"ARROW1"


_POOL_CHECKED = False


def _arrow():
    import pyarrow as pa

    global _POOL_CHECKED
    if not _POOL_CHECKED:
        _POOL_CHECKED = True
        # mimalloc (pyarrow's default pool) intermittently corrupts under
        # this engine's thread mix (see ballista_tpu/__init__.py). The env
        # selector set there is inert on builds without jemalloc, so
        # verify at first use and degrade to the system allocator — but
        # only when the pool choice was OURS: a user's explicit
        # ARROW_DEFAULT_MEMORY_POOL always wins.
        # ours only when the env still holds the exact value we recorded
        # at set time: the marker is inherited by child processes, where
        # a user's explicit ARROW_DEFAULT_MEMORY_POOL must win even
        # though the marker is present
        user_chose = (
            "ARROW_DEFAULT_MEMORY_POOL" in os.environ
            and os.environ["ARROW_DEFAULT_MEMORY_POOL"]
            != os.environ.get("_BALLISTA_SET_ARROW_POOL")
        )
        try:
            if (not user_chose
                    and pa.default_memory_pool().backend_name == "mimalloc"
                    and not os.environ.get("BALLISTA_ALLOW_MIMALLOC")):
                pa.set_memory_pool(pa.system_memory_pool())
        except Exception:  # noqa: BLE001 - keep whatever pool exists
            pass
    return pa


def _rows_to_arrow(batch: ColumnBatch, rows: np.ndarray):
    """Rows ``rows`` (an index vector, in the order given) of ``batch``
    as ONE pyarrow RecordBatch: each column is read once (values and
    validity under one ``device.block`` span), gathered once and encoded
    once; field metadata, the registry stamp and a utf8 column's Arrow
    dictionary are built here, so once a call. At most ONE
    full-capacity host copy is live beside the gathered columns."""
    pa = _arrow()
    from ..observability.tracing import trace_span

    arrays = []
    fields = []
    # bounded per-batch column conversion; the chunk loop in
    # write_arrow carries the cancel check
    # ballista: ignore[cancel-coverage]
    for f, col in zip(batch.schema.fields, batch.columns):
        with trace_span("device.block", site="ipc.batch_to_arrow",
                        col=f.name):
            hv = np.asarray(col.values)
            hval = (None if col.validity is None
                    else np.asarray(col.validity))
        vals = hv.take(rows, axis=0)
        del hv
        nulls = None
        if hval is not None:
            nulls = ~hval.take(rows)
        del hval
        meta = {b"ballista.kind": f.dtype.kind.encode(),
                b"ballista.scale": str(f.dtype.scale).encode()}
        if f.dtype.kind == "utf8":
            if col.dictionary is None:
                raise IoError(f"utf8 column {f.name} without dictionary")
            # registry stamp (entry:version:epoch): a reader in this or
            # any sibling process resolves the SAME interned instance
            # instead of re-hydrating values from the wire
            from .. import columnar_registry

            stamp = columnar_registry.REGISTRY.stamp_of(col.dictionary)
            if stamp is not None:
                meta[b"ballista.dict"] = stamp.encode()
            codes = pa.array(vals.astype(np.int32, copy=False), mask=nulls)
            dict_vals = pa.array(
                [str(v) for v in col.dictionary.values], type=pa.string()
            )
            arr = pa.DictionaryArray.from_arrays(codes, dict_vals)
        elif f.dtype.kind == "list":
            # fixed-size list: (rows, length) physical array -> real Arrow
            # FixedSizeListArray (element kind/scale ride in metadata so
            # decimal elements decode without Arrow decimal types)
            meta[b"ballista.element_kind"] = f.dtype.element.kind.encode()
            meta[b"ballista.element_scale"] = str(
                f.dtype.element.scale).encode()
            flat = pa.array(vals.reshape(-1))
            arr = pa.FixedSizeListArray.from_arrays(
                flat, f.dtype.length,
                mask=None if nulls is None else pa.array(nulls))
        else:
            arr = pa.array(vals, mask=nulls)
        fields.append(pa.field(f.name, arr.type, True, meta))
        arrays.append(arr)
    return pa.record_batch(arrays, schema=pa.schema(fields))


def batch_to_arrow(batch: ColumnBatch):
    """Compact a ColumnBatch to a pyarrow RecordBatch (live rows only).

    Every D2H fetch (selection, then each column's buffers) runs under
    a ``device.block`` span, so shuffle-write sync time lands in the
    profiler's ``device_blocked`` lane instead of hiding inside the
    write lane. Fetches stay per-column (not one batched hoist): at
    most ONE full-capacity host copy is live beside the compacted
    outputs, the pre-span memory shape. 1 + columns blocking reads."""
    from ..observability.tracing import trace_span

    with trace_span("device.block", site="ipc.batch_to_arrow"):
        mask = np.asarray(batch.selection)
    return _rows_to_arrow(batch, np.flatnonzero(mask))


def partition_to_arrow(batch: ColumnBatch, dest, n_out: int):
    """``batch`` split ``n_out`` ways in ONE pass: ``n_out`` record
    batches, record batch ``q`` equal to
    ``batch_to_arrow(batch.with_selection(selection & (pids == q)))``.

    ``dest`` is the batch's destination vector, a device array of its
    capacity: a live row's destination, ``n_out`` for a dead row (the
    executor's ``shuffle.dest`` program). One blocking read of ``dest``
    and one of each column (:func:`_rows_to_arrow`), so 1 + columns
    whatever ``n_out`` is; ONE permutation shared by all columns, a STABLE sort of ``dest``
    (numpy's radix path for the one- and two-byte types), so the rows of
    a destination keep the batch's order as a mask would keep it; rows
    a destination counted from the same vector. Every column is
    gathered once over the live rows and destination ``q`` is the
    contiguous slice ``[start_q, start_q + count_q)`` of the one record
    batch, sharing its buffers (the IPC writer truncates a slice to its
    window, as it does for ``_iter_chunked``'s). Host memory: one
    column's full-capacity copy plus ONE batch's gathered columns, held
    until the caller has written the last slice."""
    from ..observability.tracing import trace_span

    with trace_span("device.block", site="ipc.batch_to_arrow", n_out=n_out):
        dest = np.asarray(dest)
    counts = np.bincount(dest, minlength=n_out + 1)[:n_out]
    ends = np.cumsum(counts)
    live = int(ends[-1])
    whole = _rows_to_arrow(batch, np.argsort(dest, kind="stable")[:live])
    return [whole.slice(int(end - n), int(n))
            for end, n in zip(ends, counts)]


def _iter_chunked(rb, chunk_bytes: int):
    """Split one Arrow record batch into row slices of at most
    ``chunk_bytes`` (estimated from the batch's mean bytes/row). Slices
    share the parent's buffers; the IPC writer truncates them to the
    slice window on write, so the file carries bounded record batches."""
    n = rb.num_rows
    if n == 0 or rb.nbytes <= chunk_bytes:
        yield rb
        return
    rows = max(int(chunk_bytes / max(rb.nbytes / n, 1e-9)), 1)
    for lo in range(0, n, rows):
        yield rb.slice(lo, min(rows, n - lo))


class _ColumnStatsAcc:
    """Incremental per-column {null_count, distinct_count, min, max}
    accumulator — the streaming replacement for the old whole-table
    stats pass (reference declares ColumnStats but never fills it,
    ballista.proto:478-485). min/max merge per record batch via
    pyarrow's vectorized kernels; distinct_count stays exact for
    dictionary columns by unioning the OBSERVED code sets (codes map
    1:1 to values within one dictionary), and degrades to -1 when a
    stream carries replacement dictionaries."""

    def __init__(self):
        self._cols: Optional[Dict[str, dict]] = None

    def update(self, rb) -> None:
        pa = _arrow()
        import pyarrow.compute as pc

        if self._cols is None:
            self._cols = {
                name: {"null": 0, "min": None, "max": None,
                       "codes": set(), "first_dict": None, "multi": False}
                for name in rb.schema.names
            }
        # bounded per-record-batch stats merge; callers' chunk loops
        # carry the cancel check
        # ballista: ignore[cancel-coverage]
        for i, name in enumerate(rb.schema.names):
            st = self._cols[name]
            col = rb.column(i)
            st["null"] += int(col.null_count)
            try:
                typ = col.type
                if pa.types.is_dictionary(typ):
                    if st["first_dict"] is None:
                        st["first_dict"] = col.dictionary
                    elif not st["multi"] and not (
                            col.dictionary is st["first_dict"]
                            or col.dictionary.equals(st["first_dict"])):
                        st["multi"] = True
                    if not st["multi"]:
                        st["codes"].update(
                            pc.unique(col.indices.drop_null()).to_pylist())
                    mm = pc.min_max(col.cast(typ.value_type))
                else:
                    st["codes"] = None
                    mm = pc.min_max(col)
                mn, mx = mm["min"].as_py(), mm["max"].as_py()
                if mn is not None:
                    mn, mx = _norm_stat(mn), _norm_stat(mx)
                    st["min"] = mn if st["min"] is None else min(st["min"], mn)
                    st["max"] = mx if st["max"] is None else max(st["max"], mx)
            except Exception:  # noqa: BLE001 - stats stay partial
                pass

    def rows(self) -> List[Dict]:
        out: List[Dict] = []
        for name, st in (self._cols or {}).items():
            entry: Dict = {"name": name, "null_count": st["null"],
                           "distinct_count": -1}
            if st["codes"] is not None and not st["multi"]:
                entry["distinct_count"] = len(st["codes"])
            if st["min"] is not None:
                entry["min"] = st["min"]
                entry["max"] = st["max"]
            out.append(entry)
        return out


class PartitionWriter:
    """Incremental Arrow-IPC STREAM writer for partition/shuffle files.

    The streaming replacement for materialize-then-write: callers push
    ColumnBatches as the plan produces them and each is converted,
    sliced to at most ``BALLISTA_SHUFFLE_CHUNK_BYTES`` record batches
    and written immediately — peak host memory is one chunk, not one
    partition. Every chunk write checks the thread's cancel token (a
    fired ``ctx.cancel()``/deadline aborts a multi-GB write mid-file)
    and charges the shuffle memory governor transiently so the
    in-flight gauge covers the write side too.

    tmp+rename semantics are preserved: concurrent writers of the same
    deterministic path (e.g. a speculative duplicate task) can never
    leave a half-written file visible to a fetching consumer. ``close``
    on a writer that saw no batches synthesizes one empty record batch
    from ``schema`` (or raises when none was given), matching the old
    empty-partition file shape."""

    def __init__(self, path: str, schema: Optional[Schema] = None,
                 chunk_bytes: Optional[int] = None,
                 compute_column_stats: bool = False):
        from ..distributed import spill as _spill

        self._pa = _arrow()
        self.path = path
        self._schema = schema
        self._chunk_bytes = chunk_bytes or _spill.shuffle_chunk_bytes()
        self._stats = _ColumnStatsAcc() if compute_column_stats else None
        os.makedirs(os.path.dirname(path), exist_ok=True)
        self._tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        self._sink = None
        self._writer = None
        self.num_rows = 0
        self.num_batches = 0
        self.write_seconds = 0.0
        self._done = False

    def write_batch(self, batch: ColumnBatch) -> None:
        self.write_arrow(batch_to_arrow(batch))

    def write_arrow(self, rb) -> None:
        from ..distributed import spill as _spill
        from ..lifecycle import check_cancel

        gov = _spill.governor()
        for piece in _iter_chunked(rb, self._chunk_bytes):
            # chunk-level cancellation: deadlines/ctx.cancel() abort
            # inside a large partition write, not after it
            check_cancel()
            nbytes = int(piece.nbytes)
            gov.charge(nbytes)
            try:
                t0 = time.perf_counter()
                if self._writer is None:
                    self._sink = self._pa.OSFile(self._tmp, "wb")
                    self._writer = self._pa.ipc.new_stream(
                        self._sink, piece.schema)
                self._writer.write_batch(piece)
                self.write_seconds += time.perf_counter() - t0
            finally:
                gov.release(nbytes)
            self.num_batches += 1
            self.num_rows += piece.num_rows
            if self._stats is not None:
                self._stats.update(piece)

    def close(self) -> Dict[str, int]:
        if self._done:
            raise IoError(f"partition writer already closed: {self.path}")
        if self._writer is None:
            if self._schema is None:
                raise IoError("no batches to write")
            from ..columnar import empty_batch

            self.write_batch(empty_batch(self._schema))
        try:
            self._writer.close()
            self._sink.close()
            os.replace(self._tmp, self.path)
        except BaseException:
            self.abort()
            raise
        self._done = True
        out = {
            "num_rows": self.num_rows,
            "num_batches": self.num_batches,
            "num_bytes": os.path.getsize(self.path),
        }
        if self._stats is not None:
            out["columns"] = self._stats.rows()
        return out

    def abort(self) -> None:
        """Best-effort cleanup for failed writes: close handles, drop
        the tmp file (idempotent)."""
        self._done = True
        for h in (self._writer, self._sink):
            try:
                if h is not None:
                    h.close()
            except Exception:  # noqa: BLE001 - already broken
                pass
        try:
            os.unlink(self._tmp)
        except OSError:
            pass


def write_partition(path: str, batches: List[ColumnBatch],
                    compute_column_stats: bool = True) -> Dict[str, int]:
    """Write batches to an Arrow IPC stream file; returns PartitionStats
    dict (reference: PartitionStats {num_rows, num_batches, num_bytes},
    ballista.proto:478-485) plus per-column selectivity stats unless
    ``compute_column_stats`` is off (the n_out-way shuffle write path
    turns it off: per-file column stats there have no consumer and a
    64-way shuffle would pay 64 stat passes per task). Thin list-based
    wrapper over :class:`PartitionWriter`."""
    from ..lifecycle import check_cancel

    w = PartitionWriter(path, compute_column_stats=compute_column_stats)
    try:
        for b in batches:
            # batch-level cancellation on top of write_arrow's
            # chunk-level checks (w is dynamic, so the analyzer cannot
            # follow the call)
            check_cancel()
            w.write_batch(b)
        return w.close()
    except BaseException:
        w.abort()
        raise


def _norm_stat(v):
    """Normalize a pyarrow .as_py() scalar to the physical repr the
    proto carries (dates -> epoch days)."""
    import datetime as _dt

    if isinstance(v, _dt.datetime):
        v = v.date()
    if isinstance(v, _dt.date):
        return (v - _dt.date(1970, 1, 1)).days
    return v


def _fixed_width_view(arr) -> np.ndarray:
    """An Arrow integer/float array without nulls as a zero-copy numpy
    view of its data buffer (which the view keeps alive), made WITHOUT
    leaving the GIL. ``Array.to_numpy`` gives the same view but drops
    the GIL for it, and a thread that drops the GIL takes it back behind
    every other thread that runs Python (up to the 5 ms switch interval
    each): beside three other tasks that was 84% of a shuffle read
    (PERF.md, PR 45), once a column of every record batch. Anything else
    (booleans are bit-packed; a column with nulls) goes through
    ``to_numpy``."""
    pa = _arrow()
    t = arr.type
    if arr.null_count or not (pa.types.is_integer(t)
                              or pa.types.is_floating(t)):
        return arr.to_numpy(zero_copy_only=False)
    dtype = np.dtype(t.to_pandas_dtype())
    data = arr.buffers()[1]
    if data is None or not len(arr):
        return np.zeros(0, dtype=dtype)
    return np.frombuffer(data, dtype=dtype, count=len(arr),
                         offset=arr.offset * dtype.itemsize)


def decode_fixed_size_list(chunk) -> np.ndarray:
    """FixedSizeListArray chunk -> (rows, width) ndarray of flat values.

    ``.values`` spans all slots (incl. null rows), so the reshape stays
    aligned with the row axis — but it ignores a slice offset on the
    chunk (an Arrow slice adjusts offset/length only, the child stays
    whole), so slice the flat child to this chunk's window first.
    In-repo IPC files always arrive unsliced (serialization materializes
    slices); the offset handling protects direct/zero-copy producers.
    """
    width = chunk.type.list_size
    flat = _fixed_width_view(chunk.values)
    off = chunk.offset
    flat = flat[off * width:(off + len(chunk)) * width]
    return flat.reshape(len(chunk), width)


def _same_buffers(a, b) -> bool:
    """Two Arrow arrays over the very same memory (a stream reader hands
    every record batch its dictionary in a new wrapper): equal without
    ``equals``, which compares the values outside the GIL."""
    if len(a) != len(b) or a.offset != b.offset:
        return False
    return [x and x.address for x in a.buffers()] == \
        [x and x.address for x in b.buffers()]


class _ChunkStream(_io.RawIOBase):
    """File-like adapter over an iterator of byte chunks — lets
    pyarrow's stream reader pull wire/spill chunks on demand, so decode
    consumes the transfer incrementally instead of requiring one
    contiguous whole-partition buffer."""

    def __init__(self, chunks: Iterable[bytes]):
        self._it = iter(chunks)
        self._buf = b""
        self._eof = False

    def readable(self) -> bool:
        return True

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            parts = [self._buf] + list(self._it)
            self._buf = b""
            self._eof = True
            return b"".join(parts)
        while len(self._buf) < n and not self._eof:
            try:
                self._buf += next(self._it)
            except StopIteration:
                self._eof = True
        out, self._buf = self._buf[:n], self._buf[n:]
        return out


def open_arrow_reader(source):
    """Open an Arrow IPC source (path, bytes, or file-like) in either
    layout: legacy random-access FILE format (``ARROW1`` magic) or the
    streaming STREAM format the chunked shuffle writers emit. Returns a
    pyarrow reader exposing ``schema`` / ``read_all()`` /
    ``read_next_batch()``."""
    pa = _arrow()
    if isinstance(source, (str, os.PathLike)):
        with open(source, "rb") as fh:
            head = fh.read(len(ARROW_FILE_MAGIC))
        src = pa.memory_map(str(source), "r")
    elif isinstance(source, (bytes, bytearray, memoryview)):
        head = bytes(source[:len(ARROW_FILE_MAGIC)])
        src = pa.BufferReader(source)
    else:  # file-like: stream format only (no seekable magic check)
        return pa.ipc.open_stream(source)
    if head == ARROW_FILE_MAGIC:
        return pa.ipc.open_file(src)
    return pa.ipc.open_stream(src)


class FilePieces(NamedTuple):
    """One shuffle file decoded WITHOUT assembling it: a column's values
    stay one numpy array a record batch, zero-copy views over the file's
    (memory-mapped) buffers wherever Arrow allows (fixed-width, no
    nulls). ``nulls[name][i]`` is record batch ``i``'s null mask, or
    ``None`` where Arrow's own ``null_count`` says it has none. utf8
    columns hold codes valid in ``dicts[name]``, the file's one
    dictionary."""

    names: List[str]
    rows: int
    #: bytes of the decoded values, all columns
    nbytes: int
    values: Dict[str, List[np.ndarray]]
    nulls: Dict[str, List[Optional[np.ndarray]]]
    dicts: Dict[str, "Dictionary | np.ndarray"]
    kinds: Dict[str, Tuple[str, int]]
    #: (dtype, row shape) a column, for a file that holds no record batch
    empties: Dict[str, Tuple[np.dtype, Tuple[int, ...]]]


def read_partition_pieces(path_or_buf) -> FilePieces:
    """Decode an IPC partition to per-record-batch views
    (:class:`FilePieces`); accepts both IPC layouts (see
    :func:`open_arrow_reader`). The shuffle reader's entry: it places
    the pieces of a whole group once (:func:`batches_from_pieces`)."""
    in_memory = isinstance(path_or_buf, (str, os.PathLike, bytes,
                                         bytearray, memoryview))
    return _decode_pieces(open_arrow_reader(path_or_buf), in_memory)


def read_partition_pieces_from_chunks(chunks: Iterable[bytes]) -> FilePieces:
    """:func:`read_partition_pieces` fed by an iterator of stream-format
    byte chunks (the flow-controlled data plane fetch, or a ChunkBuffer
    replay spanning RAM + spill files). Chunks are pulled — and can be
    released by the producer — as the decoder advances; a truncated
    stream raises pyarrow's invalid-IPC error, which shuffle readers tag
    into ShuffleFetchError."""
    pa = _arrow()
    return _decode_pieces(pa.ipc.open_stream(_ChunkStream(chunks)))


def read_partition_arrays(
    path_or_buf,
) -> Tuple[List[str], Dict[str, np.ndarray], Dict[str, np.ndarray],
           Dict[str, np.ndarray], Dict[str, Tuple[str, int]]]:
    """Read an IPC partition -> (names, arrays, null_masks, dictionaries,
    kinds).

    arrays hold PHYSICAL values (codes for utf8); dictionaries map colname ->
    np object array for utf8 columns; kinds map colname -> (kind, scale).
    Accepts both IPC layouts (see :func:`open_arrow_reader`); decode is
    incremental per record batch, so a memory-mapped stream file never
    materializes its wire bytes as one blob.
    """
    return _whole_arrays(read_partition_pieces(path_or_buf))


def read_partition_arrays_from_chunks(chunks: Iterable[bytes]):
    """:func:`read_partition_arrays` over
    :func:`read_partition_pieces_from_chunks`."""
    return _whole_arrays(read_partition_pieces_from_chunks(chunks))


def _batch_iter(reader, in_memory: bool = False):
    """The reader's record batches, each as the list of its columns, the
    thread's cancel token checked before each (per-record-batch
    cancellation at the producer, so every consumer of this iterator
    inherits it). ``in_memory``: the source is a memory map or a buffer,
    where ``read_all`` copies nothing and leaves the GIL once a FILE;
    ``read_next_batch`` leaves it once a record batch (see
    :func:`_fixed_width_view` for what that costs). A stream of wire
    chunks is pulled batch by batch, so its chunks can be released as
    the decoder advances."""
    from ..lifecycle import check_cancel

    if in_memory:
        # chunk k of every column is record batch k (an empty one too,
        # which ``Table.to_batches`` would drop with its dictionary)
        columns = reader.read_all().columns
        for k in range(columns[0].num_chunks if columns else 0):
            check_cancel()
            yield [c.chunk(k) for c in columns]
        return
    if hasattr(reader, "num_record_batches"):  # legacy FILE format
        for i in range(reader.num_record_batches):
            check_cancel()
            yield reader.get_batch(i).columns
        return
    while True:
        check_cancel()
        try:
            rb = reader.read_next_batch()
        except StopIteration:
            return
        yield rb.columns


def _decode_pieces(reader, in_memory: bool = False) -> FilePieces:
    """The one decode core: per-record-batch numpy pieces (checking the
    thread's cancel token at every batch boundary), nothing concatenated.
    A column of a record batch whose ``null_count`` is 0 costs no mask
    and, for the fixed-width types, no copy: its piece is a view of the
    reader's buffer, which the view keeps alive."""
    pa = _arrow()
    from ..lifecycle import check_cancel

    schema = reader.schema
    names = list(schema.names)
    metas = [schema.field(i).metadata or {} for i in range(len(names))]
    values: Dict[str, List[np.ndarray]] = {n: [] for n in names}
    nulls: Dict[str, List[Optional[np.ndarray]]] = {n: [] for n in names}
    # utf8 columns: per-batch (codes, dictionary) with replacement
    # detection — a stream is allowed to swap dictionaries mid-flight
    dict_state: Dict[str, dict] = {}
    rows = 0
    for columns in _batch_iter(reader, in_memory):
        # chunk-level cancellation: ctx.cancel()/deadlines abort
        # mid-partition decodes (local mmap reads included)
        check_cancel()
        rows += len(columns[0]) if columns else 0
        for name, col in zip(names, columns):
            nm = None
            if pa.types.is_dictionary(col.type):
                idx = col.indices
                if idx.null_count:
                    nm = np.asarray(idx.is_null())
                    codes = np.where(nm, 0, idx.fill_null(0).to_numpy(
                        zero_copy_only=False)).astype(np.int32)
                else:
                    codes = _fixed_width_view(idx).astype(
                        np.int32, copy=False)
                st = dict_state.setdefault(
                    name, {"first": col.dictionary, "multi": False,
                           "dicts": []})
                if not st["multi"] and not (
                        _same_buffers(col.dictionary, st["first"])
                        or col.dictionary.equals(st["first"])):
                    st["multi"] = True
                st["dicts"].append(col.dictionary)
                values[name].append(codes)
            elif pa.types.is_fixed_size_list(col.type):
                if col.null_count:
                    nm = np.asarray(col.is_null())
                values[name].append(decode_fixed_size_list(col))
            elif not col.null_count:
                values[name].append(_fixed_width_view(col))
            else:
                nm = np.asarray(col.is_null())
                if pa.types.is_integer(col.type):
                    # stay in integer domain: to_numpy on a nullable int
                    # array converts to float64, corrupting scaled-
                    # decimal/int64 values above 2^53
                    values[name].append(
                        col.fill_null(0).to_numpy(zero_copy_only=False))
                else:
                    vals = col.to_numpy(zero_copy_only=False)
                    values[name].append(
                        np.where(nm, 0, np.nan_to_num(vals)))
            nulls[name].append(nm)

    dicts: Dict[str, np.ndarray] = {}
    kinds: Dict[str, Tuple[str, int]] = {}
    empties: Dict[str, Tuple[np.dtype, Tuple[int, ...]]] = {}
    for i, name in enumerate(names):
        meta = metas[i]
        kind = meta.get(b"ballista.kind", b"").decode() or None
        scale = int(meta.get(b"ballista.scale", b"0") or 0)
        ftype = schema.field(i).type
        if pa.types.is_dictionary(ftype):
            values[name], dicts[name] = _finish_dict_column(
                values[name], dict_state.get(name), meta)
            kinds[name] = ("utf8", 0)
            empties[name] = (np.dtype(np.int32), ())
        elif pa.types.is_fixed_size_list(ftype):
            ekind = (meta.get(b"ballista.element_kind", b"").decode()
                     or str(ftype.value_type))
            escale = int(meta.get(b"ballista.element_scale", b"0") or 0)
            kinds[name] = (f"list:{ekind}", escale)
            empties[name] = (np.dtype(ftype.value_type.to_pandas_dtype()),
                             (ftype.list_size,))
        else:
            kinds[name] = (kind or str(ftype), scale)
            empties[name] = (np.dtype(ftype.to_pandas_dtype()), ())
    nbytes = sum(int(a.nbytes) for ps in values.values() for a in ps)
    return FilePieces(names, rows, nbytes, values, nulls, dicts, kinds,
                      empties)


def _whole_arrays(fp: FilePieces):
    """:class:`FilePieces` laid end to end a column: the
    ``(names, arrays, null_masks, dictionaries, kinds)`` of
    :func:`read_partition_arrays` (result fetch, tests). A piece without
    a mask reads as all-valid."""
    arrays: Dict[str, np.ndarray] = {}
    nulls: Dict[str, np.ndarray] = {}
    for name in fp.names:
        ps = fp.values[name]
        dtype, tail = fp.empties[name]
        arrays[name] = (ps[0] if len(ps) == 1
                        else np.concatenate(ps) if ps
                        else np.zeros((0,) + tail, dtype=dtype))
        nps = [nm if nm is not None else np.zeros(len(p), dtype=bool)
               for p, nm in zip(ps, fp.nulls[name])]
        nulls[name] = (nps[0] if len(nps) == 1
                       else np.concatenate(nps) if nps
                       else np.zeros(0, dtype=bool))
    return fp.names, arrays, nulls, fp.dicts, fp.kinds


def _finish_dict_column(codes: List[np.ndarray], st: Optional[dict],
                        meta: dict):
    """One utf8 column of a file: its per-record-batch codes and the ONE
    dictionary they are valid in. Single-dictionary streams (the
    writers' contract) resolve the registry stamp or adopt the values
    once and keep their codes as decoded; replacement dictionaries remap
    every batch onto the registry's sorted union."""
    from .. import columnar_registry as _reg

    if st is None or not codes:
        return [], np.asarray([], dtype=object)
    if st["multi"]:
        parts = [
            (c, np.asarray(d.to_pylist(), dtype=object))
            for c, d in zip(codes, st["dicts"])
        ]
        unified, remapped = _reg.unify_parts(parts)
        return [r.astype(np.int32, copy=False) for r in remapped], unified
    # a registry stamp resolves to the live interned Dictionary
    # (content-verified by epoch) without touching the shipped values;
    # otherwise adopt them once per content epoch so every part/read of
    # equal content shares ONE instance
    stamp = meta.get(b"ballista.dict", b"").decode() or None
    resolved = _reg.REGISTRY.resolve(stamp)
    if resolved is None and _reg.enabled():
        resolved = _reg.REGISTRY.adopt(
            stamp,
            np.asarray(st["first"].to_pylist(), dtype=object))
    if resolved is not None:
        return codes, resolved
    # registry off: legacy raw value array
    return codes, np.asarray(st["first"].to_pylist(), dtype=object)


def unify_dictionaries(
    dicts: List["Dictionary | np.ndarray"]
) -> Tuple[Dictionary, List[Optional[np.ndarray]]]:
    """The dictionaries of several producers' files (Dictionary, or raw
    values from a legacy file) -> (shared Dictionary, one int32 remap
    table a file, ``None`` where the file's codes are already valid in
    it). Sorted union keeps codes ordinal. Routed through the dictionary
    registry: producers of one table resolve to ONE interned instance
    (no remap at all), version chains remap through cached integer
    tables, and only unregistered content pays a (cached) sorted
    union."""
    from ..observability.tracing import trace_span
    from .. import columnar_registry as _reg

    if not dicts:
        return Dictionary([]), []
    with trace_span("host.dictionary", site="ipc.unify", n_parts=len(dicts)):
        # raw value arrays are adopted first so equal producers still
        # collapse to one instance; registry off, the legacy union
        # inside ``unify`` takes plain Dictionaries
        target, remaps = _reg.unify([
            d if isinstance(d, Dictionary)
            else _reg.REGISTRY.adopt(None, d) if _reg.enabled()
            else Dictionary(d)
            for d in dicts])
        return (target if target is not None else Dictionary([])), remaps


# a copy up to this size is made holding the GIL (some 0.1 ms)
_COPY_UNDER_GIL_BYTES = 1 << 20


def _copy_rows(dst: np.ndarray, src: np.ndarray) -> None:
    """``dst[...] = src`` for two arrays of one shape. numpy leaves the
    GIL for a copy, and a task thread takes it back behind every other
    thread that runs Python (see :func:`_fixed_width_view`): a record
    batch's column is some 100 KB, so where no cast is needed it is
    copied as bytes under the GIL, which a memoryview assignment is."""
    if (src.dtype == dst.dtype and src.nbytes <= _COPY_UNDER_GIL_BYTES
            and src.flags.c_contiguous and dst.flags.c_contiguous):
        if src.nbytes:
            dst.data.cast("B")[:] = src.data.cast("B")
    else:
        dst[...] = src


def _fill(bufs: List[np.ndarray], sizes: List[int],
          pieces: List[np.ndarray]) -> None:
    """``pieces`` laid end to end fill ``bufs[b][:sizes[b]]``, batch
    after batch: each piece copied once, cut where a batch ends."""
    b, at = 0, 0
    # a copy of at most a record batch's column a turn; the caller's
    # column loop checks the token
    # ballista: ignore[cancel-coverage]
    for piece in pieces:
        # ballista: ignore[cancel-coverage]
        while len(piece):
            take = min(len(piece), sizes[b] - at)
            if take:
                _copy_rows(bufs[b][at:at + take], piece[:take])
                piece, at = piece[take:], at + take
            else:   # the batch is full: the piece goes on in the next
                b, at = b + 1, 0


def batches_from_pieces(
    schema: Schema, files: List[FilePieces],
) -> Tuple[List[ColumnBatch], int]:
    """A shuffle group's files (:func:`read_partition_pieces`, producer
    order) -> (its ColumnBatches, the arrays uploaded for them).

    ONE pass: the group's rows, file after file and row after row, are
    cut into batches of at most ``DEFAULT_BATCH_CAPACITY`` rows (what a
    scan cuts at: no operator sees a larger shape from a reader than
    from a scan), the last at its ladder rung (at least half a batch
    where there are several); every batch column is ONE
    buffer of the batch's capacity in the device dtype, each piece
    copied (and cast, where the file's type differs) straight into its
    place, utf8 codes through their file's remap table onto the group's
    one dictionary; then every array of the group goes to the device in
    ONE ``jax.device_put``. A group with pieces but no rows is one empty
    batch, so the schema and its dictionaries still travel."""
    import jax

    from ..lifecycle import check_cancel
    from ..observability.memory import track_host_bytes

    if not files:
        return [], 0
    CAP = DEFAULT_BATCH_CAPACITY
    rows = sum(fp.rows for fp in files)
    # rows a batch, and each batch's capacity: full batches at CAP, the
    # rest at the rung shuffle reads have always entered at. The rest of
    # a group of SEVERAL batches takes at least half a batch: what is
    # laid end to end downstream (a join's build) then has k or k + 1/2
    # batches of slots and no other size, and every size is a program
    # to compile (a ``lax.sort`` 17-190 s each; PERF.md, PR 45: the
    # served SF10 joins keep the five sort capacities they had)
    sizes = [min(CAP, rows - lo) for lo in range(0, rows, CAP)] or [0]
    least = CAP // 2 if len(sizes) > 1 else 1
    caps = [n if n == CAP else min(bucket_capacity(max(n, least)), CAP)
            for n in sizes]

    # shuffle-read host buffers: transient, but the peak matters — the
    # memory plane attributes them separately from scan parse buffers
    with track_host_bytes("shuffle", sum(fp.nbytes for fp in files)):
        # a column: (a buffer a batch, a validity a batch or None)
        placed: List[Tuple[List[np.ndarray],
                           Optional[List[np.ndarray]]]] = []
        dicts: List[Optional[Dictionary]] = []
        for f in schema.fields:
            dtype = f.dtype.device_dtype()
            tail = (f.dtype.length,) if f.dtype.kind == "list" else ()
            bufs = [np.empty((cap,) + tail, dtype=dtype) for cap in caps]
            # validity only for a column that has a null somewhere in the
            # group: padding and unmasked pieces read as valid
            valid = None
            if any(nm is not None for fp in files
                   for nm in fp.nulls[f.name]):
                valid = [np.ones(cap, dtype=bool) for cap in caps]
            remaps: List[Optional[np.ndarray]] = [None] * len(files)
            union = None
            if f.dtype.kind == "utf8":
                union, remaps = unify_dictionaries(
                    [fp.dicts[f.name] for fp in files])
            # per-column cancellation: the placement copies the group's
            # bytes, real work a fired token must be able to stop
            check_cancel()
            _fill(bufs, sizes, [
                piece if remap is None else remap[piece]
                for fp, remap in zip(files, remaps)
                for piece in fp.values[f.name]])
            if valid is not None:
                _fill(valid, sizes, [
                    np.ones(len(piece), dtype=bool) if nm is None else ~nm
                    for fp in files
                    for piece, nm in zip(fp.values[f.name],
                                         fp.nulls[f.name])])
            for buf, n in zip(bufs, sizes):
                buf[n:] = 0
            placed.append((bufs, valid))
            dicts.append(union)
        # the group as one tree of host arrays, a batch: (its columns'
        # (values, validity or None), selection, row count)
        host = []
        for b, (cap, n) in enumerate(zip(caps, sizes)):
            sel = np.zeros(cap, dtype=bool)
            sel[:n] = True
            host.append(([(bufs[b], None if valid is None else valid[b])
                          for bufs, valid in placed], sel, np.int32(n)))
        dev = jax.device_put(host)
    return [
        ColumnBatch(schema,
                    [Column(values, f.dtype, validity, d)
                     for (values, validity), f, d
                     in zip(cols, schema.fields, dicts)],
                    sel, n)
        for cols, sel, n in dev
    ], len(jax.tree_util.tree_leaves(host))
