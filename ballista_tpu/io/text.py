"""Delimited text sources: TPC-H ``.tbl`` ('|'-separated) and CSV.

Equivalent of the reference's CSV scan path (reference:
rust/client/src/context.rs:88-108 read_csv; benchmark .tbl registration at
rust/benchmarks/tpch/src/main.rs:128-155). Parsing currently rides pandas'
C reader; the native C++ scanner in ballista_tpu/native replaces it on the
hot path when built.

Partitioning: a directory scans one file per partition (the reference's
testdata layout, rust/scheduler/testdata/*/partition{0,1}.tbl); a single
file is one partition, optionally chunked into multiple batches.

Dictionaries are built lazily per string column over ALL partitions at
first use (sorted + interned), so codes are ordinal and comparable across
every batch of the table.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..columnar import ColumnBatch, Dictionary, DEFAULT_BATCH_CAPACITY
from ..compile import bucket_capacity
from ..datatypes import Schema
from ..errors import IoError
from ..ingest.phases import phase
from ..logical import TableSource

# Files larger than this stream through the native scanner in byte-range
# chunks (bounded RAM at any scale factor) instead of one whole-file
# parse. Streaming pays one extra pre-pass over the file to build
# table-wide utf8 dictionaries, so the threshold is set where whole-file
# RAM actually hurts (~1GB of text -> a few GB resident), keeping
# SF<=1-class files on the single-parse fast path.
STREAM_CHUNK_BYTES = 1 << 30


def _list_files(path: str, suffixes=(".tbl", ".csv", ".txt", ".dat")) -> List[str]:
    if os.path.isdir(path):
        out = sorted(
            os.path.join(path, f)
            for f in os.listdir(path)
            if f.endswith(suffixes) or "." not in f
        )
        if not out:
            raise IoError(f"no data files under {path}")
        return out
    if not os.path.exists(path):
        raise IoError(f"no such path: {path}")
    return [path]


class DelimitedSource(TableSource):
    def __init__(
        self,
        path: str,
        schema: Schema,
        delimiter: str,
        has_header: bool = False,
        trailing_delimiter: bool = False,
        batch_capacity: int = DEFAULT_BATCH_CAPACITY,
    ):
        self._path = path
        self._schema = schema
        self._delim = delimiter
        self._header = has_header
        self._trailing = trailing_delimiter
        self._capacity = batch_capacity
        self._files = _list_files(path)
        self._dicts: Dict[str, Dictionary] = {}
        # dictionary-registry entry identity: every source instance
        # over the same table files (re-registrations, self-join
        # re-scans, executor tasks in one process) shares interned
        # dictionaries, so codes are comparable by construction
        from .. import columnar_registry

        self._dict_key_base = columnar_registry.file_entry_key(
            "text", path, self._files)
        # parallel ingest runs partitions of one table (and self-joined
        # re-scans) concurrently: dictionary builds must publish exactly
        # one instance per column (codes stay comparable across batches
        # without union remaps). RLock: _dictionary_for may call
        # _build_native_dicts.
        self._dict_lock = threading.RLock()

    # -- TableSource --------------------------------------------------------

    def table_schema(self) -> Schema:
        return self._schema

    def num_partitions(self) -> int:
        return len(self._files)

    def source_descriptor(self) -> dict:
        return {
            "kind": "tbl" if self._delim == "|" else "csv",
            "path": self._path,
            "delimiter": self._delim,
            "has_header": self._header,
        }

    def estimated_rows(self) -> Optional[int]:
        """file sizes / sampled average line length (no full read)."""
        if not self._files:
            return 0
        try:
            with open(self._files[0], "rb") as fh:
                sample = fh.read(1 << 16)
        except OSError:
            return None
        lines = sample.count(b"\n")
        if lines == 0:
            return None
        avg = len(sample) / lines
        total = sum(os.path.getsize(f) for f in self._files)
        return int(total / avg)

    # -- scanning -----------------------------------------------------------

    def _read_pandas(self, path: str, names: List[str], usecols: List[int]):
        import pandas as pd

        # integer columns parse as nullable Int64: exact above 2^53 AND
        # NA-capable (a bare float64 parse would silently round large
        # ints the moment any row has an empty field)
        dtype = {}
        for i in usecols:
            if i < len(self._schema):
                f = self._schema.fields[i]
                if f.dtype.kind in ("int64", "int32"):
                    dtype[f.name] = "Int64"
        return pd.read_csv(
            path,
            sep=self._delim,
            header=0 if self._header else None,
            names=names,
            usecols=usecols,
            engine="c",
            skipinitialspace=False,
            dtype=dtype or None,
        )

    def _column_names(self) -> List[str]:
        names = list(self._schema.names())
        if self._trailing:
            names = names + ["__trailing__"]
        return names

    def _build_native_dicts(self, colnames: List[str]) -> None:
        """ONE shared native pre-pass building global sorted dictionaries
        for several utf8 columns at once, range-chunked so RAM stays
        bounded on arbitrarily large files. Only dictionary values are
        kept; per-range codes are discarded."""
        from . import native

        with self._dict_lock:
            self._build_native_dicts_locked(colnames)

    def _dict_key(self, colname: str) -> tuple:
        return self._dict_key_base + (colname,)

    def _build_native_dicts_locked(self, colnames: List[str]) -> None:
        from . import native
        from .. import columnar_registry

        need = []
        for n in colnames:
            if n in self._dicts:
                continue
            # a sibling source over the same files already paid for
            # this build: reuse the interned dictionary outright
            d = columnar_registry.REGISTRY.lookup(self._dict_key(n))
            if d is not None:
                self._dicts[n] = d
            else:
                need.append(n)
        if not need:
            return
        uniq: Dict[str, Optional[np.ndarray]] = {n: None for n in need}
        for f in self._files:
            size = os.path.getsize(f)
            off = 0
            while True:
                mb = STREAM_CHUNK_BYTES if size > STREAM_CHUNK_BYTES else -1
                _, _, fd, _ = native.scan_file(
                    f, self._schema, need, self._delim, self._header,
                    offset=off, max_bytes=mb,
                )
                for n in need:
                    u = fd.get(n)
                    if u is None or len(u) == 0:
                        continue
                    uniq[n] = (
                        u if uniq[n] is None
                        else np.unique(  # dict-ok: raw-value dict build
                            np.concatenate([uniq[n], u])))
                if mb < 0:
                    break
                off += STREAM_CHUNK_BYTES
                if off >= size:
                    break
        for n in need:
            self._dicts[n] = columnar_registry.intern(
                self._dict_key(n),
                uniq[n] if uniq[n] is not None else [])

    def _dictionary_for(self, colname: str) -> Dictionary:
        """Global sorted dictionary over all partitions (built once per
        registry entry; concurrent scans serialize on the build, and
        sibling sources over the same files share the interned
        instance)."""
        from .. import columnar_registry

        with self._dict_lock:
            if colname in self._dicts:
                return self._dicts[colname]
            d = columnar_registry.REGISTRY.lookup(self._dict_key(colname))
            if d is not None:
                self._dicts[colname] = d
                return d
            with phase("parse"):
                if self._use_native():
                    self._build_native_dicts_locked([colname])
                    return self._dicts[colname]
                uniq: Optional[np.ndarray] = None
                for f in self._files:
                    idx = self._schema.index_of(colname)
                    df = self._read_pandas(f, self._column_names(), [idx])
                    # empty fields: "" is a utf8 VALUE (native-scanner
                    # convention), not NULL
                    u = np.unique(  # dict-ok: raw-value dict build
                        df[colname].fillna("").astype(str)
                        .to_numpy(dtype=object)
                    )
                    uniq = (u if uniq is None
                            else np.unique(  # dict-ok: raw-value build
                                np.concatenate([uniq, u])))
                d = columnar_registry.intern(
                    self._dict_key(colname),
                    uniq if uniq is not None else [])
                self._dicts[colname] = d
                return d

    def _use_native(self) -> bool:
        # the native scanner does no quote handling; use it only for the
        # unquoted '|' (TPC-H .tbl) format and keep quoted CSV on pandas.
        # Types it has no kind code for (timestamps) also fall back.
        from . import native

        return (native.available() and self._delim == "|"
                and all(f.dtype.kind in native._KIND_CODES
                        for f in self._schema.fields))

    def content_signature(self) -> Optional[tuple]:
        """Re-stat'd file identity + the format knobs that change parsed
        rows — the result-cache invalidation signal for text tables."""
        from .. import columnar_registry

        return columnar_registry.file_entry_key(
            "text", self._path, self._files
        ) + (self._delim, self._header, self._trailing)

    def residency_key(self, partition: int,
                      projection=None) -> Optional[tuple]:
        from ..cache import residency

        # large files stream in byte-range chunks (bounded RAM at any
        # scale): their output would evict the whole device cache for
        # one table, so they bypass residency (key=None -> plain
        # streaming with transient batches)
        try:
            size = os.path.getsize(self._files[partition])
        except OSError:
            size = 0
        if self._use_native() and size > STREAM_CHUNK_BYTES:
            return None
        return residency.scan_key(
            "tbl" if self._delim == "|" else "csv",
            self._files[partition], partition, projection,
            extra=(self._delim, self._header, self._trailing,
                   self._capacity),
        )

    def scan(self, partition: int, projection: Optional[Sequence[str]] = None):
        from ..cache import residency

        yield from residency.serve_or_fill(
            self.residency_key(partition, projection),
            lambda: self._scan_direct(partition, projection),
            outcome_sink=self._note_scan_outcome(partition))

    def _scan_direct(self, partition: int,
                     projection: Optional[Sequence[str]] = None):
        """The uncached parse + H2D path (residency misses land here)."""
        names = projection if projection is not None else self._schema.names()
        sub_schema = self._schema.project(names)
        if self._use_native():
            size = os.path.getsize(self._files[partition])
            if size > STREAM_CHUNK_BYTES:
                yield from self._scan_native_streaming(
                    partition, names, sub_schema)
                return
            with phase("parse", path=self._files[partition]):
                n, arrays, dicts, valids = self._scan_native(partition, names)
        else:
            with phase("parse", path=self._files[partition]):
                n, arrays, dicts, valids = self._scan_pandas(partition, names)
        # chunk into fixed-capacity batches
        yield from self._emit_batches(sub_schema, n, arrays, dicts, valids)

    def _scan_native_streaming(self, partition: int, names, sub_schema):
        """Parse one partition file in byte-range chunks, remapping each
        range's utf8 codes onto the table-wide dictionaries (built by one
        shared pre-pass) and emitting batches incrementally. Peak RAM is
        O(STREAM_CHUNK_BYTES), so SF=10+ scans without materializing the
        file. Reference anchor: partitioned CSV conversion,
        rust/benchmarks/tpch/src/main.rs:196-265."""
        from . import native

        path = self._files[partition]
        size = os.path.getsize(path)
        utf8_names = [n for n in names
                      if self._schema.field(n).dtype.kind == "utf8"]
        with phase("parse", path=path, prepass="dicts"):
            self._build_native_dicts(utf8_names)
        # hoist the fixed-width dictionary views out of the chunk loop:
        # values_str() declines to cache views past its size cap, and
        # re-materializing a big dictionary per 256MB range would churn
        # exactly the memory this path exists to bound
        dict_keys = {n: self._dicts[n].values_str() for n in utf8_names}
        off = 0
        emitted = False
        while off < size:
            with phase("parse", path=path, offset=off):
                n, arrays, fdicts, valids = native.scan_file(
                    path, self._schema, list(names), self._delim,
                    self._header, offset=off, max_bytes=STREAM_CHUNK_BYTES,
                )
                off += STREAM_CHUNK_BYTES
                if n == 0:
                    continue
                dicts: Dict[str, Dictionary] = {}
                for name in utf8_names:
                    d = self._dicts[name]
                    remap = np.searchsorted(  # dict-ok: hoisted encode
                        dict_keys[name],
                        np.asarray(fdicts[name]).astype(str)
                    ).astype(np.int32)
                    arrays[name] = remap[arrays[name]].astype(np.int32)
                    dicts[name] = d
            yield from self._emit_batches(sub_schema, n, arrays, dicts,
                                          valids, force_emit=False)
            emitted = True
        if not emitted:  # empty file: one empty batch keeps contracts
            yield from self._emit_batches(sub_schema, 0, {
                n: np.zeros(0, self._schema.field(n).dtype.device_dtype())
                for n in names
            }, {n: self._dicts[n] for n in utf8_names}, None)

    def _scan_native(self, partition: int, names):
        """Native C++ scan; per-file utf8 dictionaries are remapped onto the
        table-wide union dictionary so codes stay ordinal across
        partitions. Single-file tables adopt the file dictionary directly."""
        from . import native

        n, arrays, fdicts, valids = native.scan_file(
            self._files[partition], self._schema, list(names),
            self._delim, self._header,
        )
        dicts: Dict[str, Dictionary] = {}
        for name in names:
            if self._schema.field(name).dtype.kind != "utf8":
                continue
            fvals = fdicts[name]
            if len(self._files) == 1:
                from .. import columnar_registry

                with self._dict_lock:  # one adopted instance per column
                    if name not in self._dicts:
                        self._dicts[name] = columnar_registry.intern(
                            self._dict_key(name), fvals)
                    d = self._dicts[name]
                # same file scanned twice must yield the same dict (and
                # interning may have returned a superset version); remap
                # when the file's values are not the dictionary verbatim
                if len(d) != len(fvals) or not np.array_equal(
                    d.values_str(), np.asarray(fvals).astype(str)
                ):
                    remap = d.positions_of(fvals)
                    arrays[name] = remap[arrays[name]].astype(np.int32)
            else:
                d = self._dictionary_for(name)
                remap = d.positions_of(fvals)
                arrays[name] = remap[arrays[name]].astype(np.int32)
            dicts[name] = d
        return n, arrays, dicts, valids

    def _scan_pandas(self, partition: int, names):
        idxs = [self._schema.index_of(n) for n in names]
        df = self._read_pandas(self._files[partition], self._column_names(), idxs)
        n = len(df)
        arrays: Dict[str, np.ndarray] = {}
        dicts: Dict[str, Dictionary] = {}
        valids: Dict[str, np.ndarray] = {}
        for name in names:
            field = self._schema.field(name)
            raw = df[name]  # pandas labels used columns by the given names
            # empty non-string fields are SQL NULLs (same convention as
            # the native scanner); "" stays a utf8 VALUE
            na = raw.isna().to_numpy() if field.dtype.kind != "utf8" else None
            if na is not None and na.any():
                valids[name] = ~na
                fill = ("1970-01-01"
                        if field.dtype.kind in ("date32", "timestamp_ns")
                        else 0)
                raw = raw.fillna(fill)
            if field.dtype.kind == "utf8":
                d = self._dictionary_for(name)
                vals = raw.fillna("").astype(str).to_numpy(dtype=object)
                arrays[name] = d.positions_of(vals)
                dicts[name] = d
            elif field.dtype.kind == "decimal":
                from ..columnar import decimal_to_scaled

                arrays[name] = decimal_to_scaled(
                    raw.to_numpy(dtype=np.float64), field.dtype.scale
                )
            elif field.dtype.kind == "date32":
                vals = raw.astype(str).to_numpy(dtype="datetime64[D]")
                arrays[name] = vals.astype(np.int32)
            elif field.dtype.kind == "timestamp_ns":
                vals = raw.astype(str).to_numpy(dtype="datetime64[ns]")
                arrays[name] = vals.astype(np.int64)
            else:
                arrays[name] = raw.to_numpy(dtype=field.dtype.device_dtype())
        return n, arrays, dicts, valids

    def _emit_batches(self, sub_schema, n, arrays, dicts, valids=None,
                      force_emit=True):
        """``force_emit`` guarantees at least one (possibly empty) batch;
        streaming callers emit per range and handle the empty-table case
        themselves."""
        from ..observability.memory import track_host_bytes

        # parse buffers live on host until every chunk uploaded: account
        # them under "batches" for the peak-memory breakdown (the with
        # releases on generator close too — abandoned scans included)
        parse_bytes = sum(int(getattr(a, "nbytes", 0))
                          for a in arrays.values())
        with track_host_bytes("batches", parse_bytes):
            yield from self._emit_batches_inner(sub_schema, n, arrays,
                                                dicts, valids, force_emit)

    def _emit_batches_inner(self, sub_schema, n, arrays, dicts,
                            valids=None, force_emit=True):
        # scan batches enter at canonical ladder capacities so uneven
        # files/partitions reuse a handful of compiled signatures
        from ..lifecycle import check_cancel

        cap = min(self._capacity, bucket_capacity(max(n, 1)))
        start = 0
        emitted = not force_emit
        while start < n or not emitted:
            # chunk-level cancellation: each iteration slices + uploads
            # one batch, the boundary a fired token stops at
            check_cancel()
            end = min(start + cap, n)
            chunk = {k: v[start:end] for k, v in arrays.items()}
            vchunk = (
                {k: v[start:end] for k, v in valids.items()}
                if valids else None
            )
            with phase("h2d", rows=end - start,
                       bytes=sum(v.nbytes for v in chunk.values())):
                batch = ColumnBatch.from_numpy(sub_schema, chunk, dicts,
                                               capacity=cap, validity=vchunk)
            yield batch
            emitted = True
            start = end
            if start >= n:
                break


class TblSource(DelimitedSource):
    """TPC-H dbgen output: '|' separated, trailing '|', no header."""

    def __init__(self, path: str, schema: Schema,
                 batch_capacity: int = DEFAULT_BATCH_CAPACITY):
        super().__init__(path, schema, "|", has_header=False,
                         trailing_delimiter=True, batch_capacity=batch_capacity)


class CsvSource(DelimitedSource):
    def __init__(self, path: str, schema: Schema, has_header: bool = True,
                 delimiter: str = ",",
                 batch_capacity: int = DEFAULT_BATCH_CAPACITY):
        super().__init__(path, schema, delimiter, has_header=has_header,
                         trailing_delimiter=False, batch_capacity=batch_capacity)
