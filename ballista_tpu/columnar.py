"""Columnar batch substrate: the unit of data flow between operators.

The reference engine flows Arrow ``RecordBatch`` values between DataFusion
operators and serializes them via Arrow IPC (reference:
rust/core/src/utils.rs:49-84, rust/core/src/memory_stream.rs:29-93). On TPU
the equivalent is a struct-of-arrays batch of *fixed capacity* device buffers
so every kernel sees static shapes:

- each column is a dense device array of length ``capacity`` (padded);
- a boolean ``selection`` mask says which physical rows are live — filters
  only AND into this mask, never compact on device;
- string columns are dictionary codes (int32) + a host-side interned
  ``Dictionary``;
- a batch is a registered JAX pytree, so whole operator pipelines jit/fuse
  into a single XLA program over its leaves.

Compaction (dropping dead rows) happens only at host boundaries (collect,
shuffle spill), where numpy boolean indexing is cheap.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .compile import bucket_capacity, governed
from .datatypes import DataType, Field, Schema, Utf8
from .errors import ExecutionError, SchemaError

# Default physical batch capacity (rows). Power of two keeps XLA tilings happy.
DEFAULT_BATCH_CAPACITY = 1 << 20

# Dictionary.values_str() keeps its fixed-width str view only under this
# size — a comment-scale dictionary's view would pin hundreds of MB.
_STR_CACHE_CAP_BYTES = 256 << 20

# FNV-1a constants (stable_hashes)
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def round_capacity(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= n (>= minimum).

    Power-of-two quantization balances shape reuse (every distinct
    capacity is a fresh XLA trace+compile) against padding waste (a
    coarser power-of-4 ladder was measured to DOUBLE warm execution time
    on TPC-H q18 at SF0.2 — padded rows still cost sort/scan bandwidth,
    and with the persistent compilation cache the compile side is already
    amortized)."""
    cap = minimum
    while cap < n:
        cap <<= 1
    return cap


_NARROW_LADDER = {
    np.dtype(np.int64): (np.int8, np.int16, np.int32),
    np.dtype(np.int32): (np.int8, np.int16),
}

_NARROW_WIRE: Optional[bool] = None


def _narrow_wire_enabled() -> bool:
    """Narrowing pays a host min/max pass per column; that's a win only
    when uploads cross a real device link (TPU), not on the CPU backend
    where jnp.asarray is a plain copy."""
    global _NARROW_WIRE
    if _NARROW_WIRE is None:
        _NARROW_WIRE = jax.default_backend() != "cpu"
    return _NARROW_WIRE


def _upload(arr: np.ndarray, want: np.dtype) -> jax.Array:
    """Host array -> device array of dtype ``want``, transferring the
    narrowest integer representation that holds the values and widening
    on device. Host->device bandwidth is the cold-query bottleneck
    (PCIe on a co-located host); TPC-H
    integer/decimal columns typically fit 1-2 bytes, so this cuts wire
    bytes ~3-4x for the cost of one fused device cast."""
    ladder = _NARROW_LADDER.get(arr.dtype)
    if ladder is None or arr.size == 0 or not _narrow_wire_enabled():
        return jnp.asarray(arr)
    lo = arr.min()
    hi = arr.max()
    for narrow in ladder:
        info = np.iinfo(narrow)
        if info.min <= lo and hi <= info.max:
            fn = governed(
                ("wire.widen", np.dtype(narrow).name, np.dtype(want).name),
                lambda _w=np.dtype(want): (lambda a: a.astype(_w)),
            )
            return fn(jnp.asarray(arr.astype(narrow)))
    return jnp.asarray(arr)


# ---------------------------------------------------------------------------
# Dictionary (host-side string table)
# ---------------------------------------------------------------------------


class Dictionary:
    """Interned host-side string table for a dictionary-encoded column.

    Identity-hashed: two scans of the same file share one instance, so it can
    ride in pytree aux-data without defeating jit caching.
    """

    __slots__ = ("values", "_index", "_tracked_bytes", "_str_cache",
                 "_hash_cache", "_str_exact", "_reg_entry_id",
                 "_reg_version", "_reg_epoch")

    def __init__(self, values: Sequence[str]):
        self.values: np.ndarray = np.asarray(list(values), dtype=object)
        self._index: Dict[str, int] = {v: i for i, v in enumerate(self.values)}
        # lazily-computed caches + dictionary-registry identity
        # (columnar_registry.py stamps entry/version/epoch on members)
        self._str_cache: Optional[np.ndarray] = None
        self._hash_cache: Optional[np.ndarray] = None
        self._str_exact: Optional[bool] = None
        self._reg_entry_id: Optional[str] = None
        self._reg_version: Optional[int] = None
        self._reg_epoch: Optional[str] = None
        # memory accounting (observability/memory.py): dictionaries are
        # the dominant host-resident string mass — ~pointer array +
        # index dict entry + string storage per value (estimate, not an
        # allocator truth; released in __del__)
        self._tracked_bytes = int(self.values.nbytes) + 120 * len(self.values)
        from .observability import memory as _obs_memory

        _obs_memory.record_host_bytes("dictionaries", self._tracked_bytes)

    def __del__(self):
        try:
            from .observability import memory as _obs_memory

            _obs_memory.release_host_bytes("dictionaries",
                                           self._tracked_bytes)
        except Exception:  # noqa: BLE001 - interpreter teardown
            pass

    def __len__(self) -> int:
        return len(self.values)

    def code_of(self, s: str) -> int:
        """Code for string s, or -1 if absent (comparison can short-circuit)."""
        return self._index.get(s, -1)

    def lookup(self, codes: np.ndarray) -> np.ndarray:
        out = np.empty(len(codes), dtype=object)
        ok = (codes >= 0) & (codes < len(self.values))
        out[ok] = self.values[codes[ok]]
        out[~ok] = None
        return out

    @staticmethod
    def encode(strings: Sequence[str]) -> Tuple["Dictionary", np.ndarray]:
        uniq, codes = np.unique(np.asarray(strings, dtype=object), return_inverse=True)
        return Dictionary(uniq), codes.astype(np.int32)

    # -- cached views / search primitives ----------------------------------
    #
    # Every host-side string operation funnels through these so the
    # fixed-width str materialization and the per-value hash pass are
    # paid ONCE per immutable instance instead of once per call site
    # (join remap, concat/ipc unify and scan encode each used to
    # ``.astype(str)`` the same values on every invocation).

    def values_str(self) -> np.ndarray:
        """Fixed-width ``np.str_`` view of the (sorted) values, cached.
        Dictionaries past the cache cap recompute per call — the cached
        view for a multi-million-value comment dictionary would pin
        hundreds of MB of host RAM."""
        sv = self._str_cache
        if sv is None:
            sv = self.values.astype(str)
            if sv.nbytes <= _STR_CACHE_CAP_BYTES:
                self._cache_str_view(sv)
        return sv

    def _cache_str_view(self, sv: np.ndarray) -> None:
        """Pin a str view on the instance, keeping the 'dictionaries'
        host-memory plane honest (the view can be several times the
        object-string mass; __del__ releases the accumulated total)."""
        if self._str_cache is None:
            self._str_cache = sv
            self._track_extra(int(sv.nbytes))

    def _track_extra(self, nbytes: int) -> None:
        from .observability import memory as _obs_memory

        self._tracked_bytes += nbytes
        _obs_memory.record_host_bytes("dictionaries", nbytes)

    def positions_of(self, values) -> np.ndarray:
        """int32 code per value via one sorted search over the cached
        str view. Scan encode paths call this with values the
        dictionary was built FROM (presence guaranteed); absent values
        get the insertion position, exactly like the searchsorted
        calls this replaces."""
        vals = np.asarray(values)
        if vals.dtype.kind != "U":
            vals = vals.astype(str)
        return np.searchsorted(self.values_str(), vals).astype(np.int32)

    def code_range(self, s: str) -> Tuple[int, int]:
        """(left, right) insertion bounds of ``s`` in code space —
        string ordering predicates compile to code comparisons against
        these (kernels/expr_eval.py)."""
        sv = self.values_str()
        return (int(np.searchsorted(sv, s, side="left")),
                int(np.searchsorted(sv, s, side="right")))

    def stable_hashes(self) -> np.ndarray:
        """int64 FNV-1a hash per dictionary value — STABLE across processes
        and dictionary encodings, so hash partitioning of utf8 columns
        agrees between independent producers (codes are producer-local;
        string hashes are not).

        Vectorized: the hash recurrence runs per BYTE POSITION over all
        values at once (a max-width pass of numpy uint64 ops) instead
        of a per-value per-byte Python loop — this sits on the shuffle
        partitioning path. Cached per immutable instance. Values the
        fixed-width str view cannot represent (trailing U+0000) hash
        through the reference scalar loop so placement never moves."""
        cached = self._hash_cache
        if cached is not None:
            return cached
        n = len(self.values)
        if n == 0:
            out = np.empty(0, dtype=np.int64)
            self._hash_cache = out
            return out
        sv = self.values_str()
        enc = np.char.encode(sv, "utf-8")
        width = enc.dtype.itemsize
        h = np.full(n, _FNV_OFFSET, dtype=np.uint64)
        if width:
            mat = enc.view(np.uint8).reshape(n, width)
            nz = mat != 0
            lengths = np.where(nz.any(axis=1),
                               width - np.argmax(nz[:, ::-1], axis=1), 0)
            for j in range(width):
                active = j < lengths
                h = np.where(active, (h ^ mat[:, j]) * _FNV_PRIME, h)
        out = h.astype(np.int64)
        # rows whose true length the str view lost (trailing NULs)
        lens = np.fromiter((len(str(v)) for v in self.values),
                           dtype=np.int64, count=n)
        mangled = np.nonzero(lens != np.char.str_len(sv))[0]
        for i in mangled:
            hh = 0xCBF29CE484222325
            for b in str(self.values[i]).encode("utf-8"):
                hh = ((hh ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
            out[i] = np.int64(np.uint64(hh))
        self._hash_cache = out
        self._track_extra(int(out.nbytes))
        return out

    @staticmethod
    def canonicalize(values: Sequence[str]) -> Tuple["Dictionary", np.ndarray]:
        """Sorted-unique dictionary + old-code -> new-code remap table.

        Comparison kernels assume dictionaries are sorted and duplicate-free;
        any derived dictionary (upper/substr/...) must pass through here.
        """
        uniq, remap = np.unique(np.asarray(values, dtype=object), return_inverse=True)
        return Dictionary(uniq), remap.astype(np.int32)

    def __hash__(self) -> int:
        return id(self)

    def __eq__(self, other) -> bool:
        return self is other

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Dictionary({len(self)} values)"


# ---------------------------------------------------------------------------
# Column
# ---------------------------------------------------------------------------


@dataclass
class Column:
    """One physical column: device values + optional validity + dtype."""

    values: jax.Array  # [capacity] device (or numpy pre-transfer)
    dtype: DataType
    validity: Optional[jax.Array] = None  # bool [capacity]; None = all valid
    dictionary: Optional[Dictionary] = None  # only for Utf8

    @property
    def capacity(self) -> int:
        return int(self.values.shape[0])

    def valid_mask(self) -> jax.Array:
        if self.validity is None:
            return jnp.ones((self.capacity,), dtype=jnp.bool_)
        return self.validity

    # -- host conversion ----------------------------------------------------

    def to_numpy_logical(self, row_mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Materialize logical values on host (decodes dicts/decimals).

        SQL NULLs (validity False) become None for strings and NaN for
        numerics — integer columns with NULLs widen to float64, matching
        pandas conventions.
        """
        from .observability.tracing import trace_span

        with trace_span("device.block", site="column.to_numpy"):
            vals = np.asarray(self.values)
            invalid = (None if self.validity is None
                       else ~np.asarray(self.validity))
        if row_mask is not None:
            vals = vals[row_mask]
            if invalid is not None:
                invalid = invalid[row_mask]
        if self.dtype.kind == "utf8" and self.dictionary is None:
            raise ExecutionError("utf8 column without dictionary")
        return decode_physical_array(
            vals, self.dtype.kind, self.dtype.scale,
            self.dictionary.values if self.dictionary is not None else None,
            invalid,
        )


# ---------------------------------------------------------------------------
# ColumnBatch
# ---------------------------------------------------------------------------


class ColumnBatch:
    """Fixed-capacity columnar batch; a JAX pytree.

    ``selection`` is the live-row mask (False for filtered-out rows AND for
    padding beyond the logical row count). ``num_rows`` is a traced i32 scalar
    with the count of live rows (kept consistent with ``selection`` by
    constructors; operators that filter must update both).
    """

    # _transient: donation eligibility (cache/donation.py) — True only
    # when the CREATOR guarantees single consumption; never flattened
    # into the pytree, consumed at most once by a donating call site
    __slots__ = ("schema", "columns", "selection", "num_rows",
                 "_transient")

    def __init__(
        self,
        schema: Schema,
        columns: Sequence[Column],
        selection: jax.Array,
        num_rows: jax.Array,
    ):
        self.schema = schema
        self.columns: Tuple[Column, ...] = tuple(columns)
        self.selection = selection
        self.num_rows = num_rows
        self._transient = False
        if len(self.columns) != len(schema):
            raise SchemaError(
                f"schema has {len(schema)} fields but {len(self.columns)} columns given"
            )

    # -- constructors -------------------------------------------------------

    @staticmethod
    def from_numpy(
        schema: Schema,
        arrays: Dict[str, np.ndarray],
        dictionaries: Optional[Dict[str, Dictionary]] = None,
        capacity: Optional[int] = None,
        validity: Optional[Dict[str, np.ndarray]] = None,
    ) -> "ColumnBatch":
        """Build a batch from host arrays of physical values, padding to
        capacity. ``validity`` maps column name -> bool array of length n
        (True = valid); columns absent from it are all-valid."""
        dictionaries = dictionaries or {}
        validity = validity or {}
        n = None
        for name, arr in arrays.items():
            if n is None:
                n = len(arr)
            elif len(arr) != n:
                raise SchemaError(f"column {name} length {len(arr)} != {n}")
        n = n or 0
        # default capacities land on the canonical bucket ladder so
        # every batch-entry boundary produces ladder shapes (explicit
        # capacities — internal small result batches — stay exact)
        cap = capacity or bucket_capacity(n)
        if cap < n:
            raise ExecutionError(f"capacity {cap} < rows {n}")
        cols: List[Column] = []
        for f in schema.fields:
            if f.name not in arrays:
                raise SchemaError(f"missing column {f.name}")
            arr = np.asarray(arrays[f.name])
            want = f.dtype.device_dtype()
            if arr.dtype != want:
                arr = arr.astype(want)
            if n < cap:
                # trailing dims (fixed-size-list element axis) pad along
                # the row axis only
                pad = np.zeros((cap - n,) + arr.shape[1:], dtype=want)
                arr = np.concatenate([arr, pad])
            va = validity.get(f.name)
            if va is not None:
                va = np.asarray(va, dtype=np.bool_)
                if len(va) < cap:  # padding rows are not valid
                    va = np.concatenate(
                        [va, np.zeros(cap - len(va), dtype=np.bool_)]
                    )
                va = _upload(va, np.bool_)
            cols.append(
                Column(_upload(arr, want), f.dtype, va,
                       dictionaries.get(f.name))
            )
        sel = np.zeros(cap, dtype=np.bool_)
        sel[:n] = True
        return ColumnBatch(
            schema, cols, jnp.asarray(sel), jnp.asarray(np.int32(n))
        )

    @staticmethod
    def from_pydict(
        schema: Schema, data: Dict[str, Sequence], capacity: Optional[int] = None
    ) -> "ColumnBatch":
        """Build from logical Python values (strings, floats for decimals...)."""
        arrays: Dict[str, np.ndarray] = {}
        dicts: Dict[str, Dictionary] = {}
        for f in schema.fields:
            vals = data[f.name]
            if f.dtype.kind == "utf8":
                d, codes = Dictionary.encode([str(v) for v in vals])
                dicts[f.name] = d
                arrays[f.name] = codes
            elif f.dtype.kind == "decimal":
                arrays[f.name] = decimal_to_scaled(
                    [float(v) for v in vals], f.dtype.scale
                )
            else:
                arrays[f.name] = np.asarray(vals, dtype=f.dtype.device_dtype())
        return ColumnBatch.from_numpy(schema, arrays, dicts, capacity)

    # -- info ---------------------------------------------------------------

    @property
    def capacity(self) -> int:
        return int(self.selection.shape[0])

    def column(self, name: str) -> Column:
        return self.columns[self.schema.index_of(name)]

    def with_columns(self, schema: Schema, columns: Sequence[Column]) -> "ColumnBatch":
        return ColumnBatch(schema, columns, self.selection, self.num_rows)

    def with_selection(
        self, selection: jax.Array, num_rows: Optional[jax.Array] = None
    ) -> "ColumnBatch":
        if num_rows is None:
            num_rows = jnp.sum(selection).astype(jnp.int32)
        return ColumnBatch(self.schema, self.columns, selection, num_rows)

    # -- host materialization ----------------------------------------------

    def to_pydict(self) -> Dict[str, np.ndarray]:
        """Compact to host: logical values of live rows only. In three
        steps, so that a collect can tell waiting for the device from
        moving the result from decoding it
        (execution.collect_physical)."""
        self.wait_ready()
        return self.decode_host(self.fetch_host())

    def _device_buffers(self) -> tuple:
        return (self.selection,
                [c.values for c in self.columns],
                [c.validity for c in self.columns])

    def wait_ready(self) -> None:
        """Block until the programs that produce this batch's buffers
        have run: the ``device.block`` span of a result fetch. The
        device-to-host copies are asked for first, as ``device_get``
        itself would, so they queue behind the programs and overlap the
        wait instead of starting after it."""
        from .observability.tracing import trace_span

        buffers = self._device_buffers()
        for leaf in jax.tree_util.tree_leaves(buffers):
            start_copy = getattr(leaf, "copy_to_host_async", None)
            if start_copy is not None:
                start_copy()
        with trace_span("device.block", site="batch.to_pydict",
                        columns=len(self.columns)):
            jax.block_until_ready(buffers)

    def fetch_host(self) -> tuple:
        """The device buffers on the host, ``(selection, values,
        validities)``, in ONE ``jax.device_get`` (async copies issued
        together, then awaited) — per-column ``np.asarray`` would
        serialize a device->host round-trip per array, which dominates
        query latency when the accelerator is remote."""
        # the copy alone: callers run wait_ready(), the span, first
        # ballista: ignore[sync-span]
        return jax.device_get(self._device_buffers())

    def decode_host(self, fetched: tuple) -> Dict[str, np.ndarray]:
        """What :meth:`fetch_host` brought, as logical values of the
        live rows by column name."""
        sel, vals, valids = fetched
        mask = np.asarray(sel)
        out: Dict[str, np.ndarray] = {}
        for f, col, v, va in zip(self.schema.fields, self.columns, vals,
                                 valids):
            if f.dtype.kind == "utf8" and col.dictionary is None:
                raise ExecutionError("utf8 column without dictionary")
            invalid = None
            if va is not None:
                invalid = ~np.asarray(va)[mask]
            if f.dtype.kind == "list":
                out[f.name] = decode_list_rows(
                    np.asarray(v)[mask], f.dtype.element.kind,
                    f.dtype.element.scale, invalid,
                )
                continue
            out[f.name] = decode_physical_array(
                np.asarray(v)[mask], f.dtype.kind, f.dtype.scale,
                col.dictionary.values if col.dictionary is not None else None,
                invalid,
            )
        return out

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame(self.to_pydict())

    def num_rows_host(self) -> int:
        return int(self.num_rows)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return (
            f"ColumnBatch(cap={self.capacity}, fields={self.schema.names()})"
        )


# ---------------------------------------------------------------------------
# pytree registration: leaves = device arrays, aux = schema + dicts
# ---------------------------------------------------------------------------


def _flatten_batch(b: ColumnBatch):
    leaves = []
    col_meta = []
    for col in b.columns:
        leaves.append(col.values)
        has_validity = col.validity is not None
        if has_validity:
            leaves.append(col.validity)
        col_meta.append((col.dtype, has_validity, col.dictionary))
    leaves.append(b.selection)
    leaves.append(b.num_rows)
    return leaves, (b.schema, tuple(col_meta))


def _unflatten_batch(aux, leaves):
    schema, col_meta = aux
    leaves = list(leaves)
    it = iter(leaves)
    cols = []
    for dtype, has_validity, dictionary in col_meta:
        values = next(it)
        validity = next(it) if has_validity else None
        cols.append(Column(values, dtype, validity, dictionary))
    selection = next(it)
    num_rows = next(it)
    return ColumnBatch(schema, cols, selection, num_rows)


jax.tree_util.register_pytree_node(ColumnBatch, _flatten_batch, _unflatten_batch)


# ---------------------------------------------------------------------------
# Host-side helpers
# ---------------------------------------------------------------------------


def decimal_to_scaled(values, scale: int) -> np.ndarray:
    """float/str decimal values -> scaled int64 using HALF-UP (away from
    zero) rounding — the same rule as the native C++ parser, so results
    never depend on which scanner read the file."""
    v = np.asarray(values, dtype=np.float64) * (10 ** scale)
    return (np.sign(v) * np.floor(np.abs(v) + 0.5)).astype(np.int64)


def decode_physical_array(
    vals: np.ndarray,
    kind: str,
    scale: int = 0,
    dictionary_values: Optional[np.ndarray] = None,
    null_mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Physical array -> logical host values, applying SQL NULL conventions
    (None for strings, NaT for dates, NaN for numerics — integers with
    NULLs widen to float64). Shared by local collect and the distributed
    result-fetch path, so the decode rules cannot drift."""
    has_nulls = null_mask is not None and bool(np.asarray(null_mask).any())
    if kind == "utf8":
        if dictionary_values is None:
            raise ExecutionError("utf8 decode requires a dictionary")
        if isinstance(dictionary_values, Dictionary):
            # IPC readers hand back registry-resolved Dictionary
            # objects; decode sees their value array either way
            dictionary_values = dictionary_values.values
        dv = np.asarray(dictionary_values, dtype=object)
        codes = np.asarray(vals).astype(np.int64)
        ok = (codes >= 0) & (codes < len(dv))
        out = np.empty(len(codes), dtype=object)
        out[ok] = dv[codes[ok]]
        out[~ok] = None
        if has_nulls:
            out[null_mask] = None
        return out
    if kind == "date32":
        out = np.asarray(vals).astype("datetime64[D]")
        if has_nulls:
            out[null_mask] = np.datetime64("NaT")
        return out
    if kind == "timestamp_ns":
        out = np.asarray(vals).astype(np.int64).astype("datetime64[ns]")
        if has_nulls:
            out[null_mask] = np.datetime64("NaT")
        return out
    if kind == "decimal":
        out = np.asarray(vals).astype(np.float64) / (10.0 ** scale)
    elif kind in ("float32", "float64"):
        out = np.asarray(vals).astype(np.float64)
    elif has_nulls:
        out = np.asarray(vals).astype(np.float64)
    else:
        return np.asarray(vals)
    if has_nulls:
        out[null_mask] = np.nan
    return out


def decode_list_rows(
    vals2d: np.ndarray,
    element_kind: str,
    element_scale: int,
    null_mask: Optional[np.ndarray] = None,
) -> np.ndarray:
    """(rows, length) physical list values -> object array of per-row 1-D
    logical vectors (None for NULL rows). Shared by local collect and the
    distributed result-fetch path, like ``decode_physical_array``."""
    arr = np.asarray(vals2d)
    flat = decode_physical_array(arr.reshape(-1), element_kind,
                                 element_scale, None, None)
    rows = np.asarray(flat).reshape(arr.shape)
    cell = np.empty(arr.shape[0], dtype=object)
    for i in range(arr.shape[0]):
        cell[i] = (None if null_mask is not None and null_mask[i]
                   else rows[i])
    return cell


def empty_batch(schema) -> "ColumnBatch":
    """Zero-row batch with the given schema (utf8 columns get empty
    dictionaries so IPC encoding works)."""
    return ColumnBatch.from_numpy(
        schema,
        {f.name: np.zeros(0, f.dtype.device_dtype()) for f in schema.fields},
        {f.name: Dictionary([]) for f in schema.fields
         if f.dtype.kind == "utf8"},
        capacity=8,
    )


def concat_pydicts(parts: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    if not parts:
        return {}
    keys = parts[0].keys()
    return {k: np.concatenate([p[k] for p in parts]) for k in keys}
