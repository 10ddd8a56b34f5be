"""Join kernels.

TPU-native replacement for the reference's ``HashJoinExec`` (reference:
rust/core/proto/ballista.proto:399-407, HashJoinExecNode with on-keys and
join type). A CPU-style linked hash table doesn't map to the MXU/VPU, so the
build side is *sorted* and the probe side searches it, every probe row at
once, in steps of 128 (``kernels/search.py``: one gathered ROW of keys a
level, two levels under the top one at 2**21 build rows, where
a binary search walked 21 dependent single-element gathers; the int64
keys are compared as two int32 planes, split inside the probe program):

- ``build_lookup`` sorts the build keys once;
- ``probe_unique`` handles the FK->PK joins that dominate TPC-H (build keys
  unique): one gather from the dense table where the build has one, else
  one stepped search + one gather; no row expansion;
- ``probe_ranges`` + ``expand_slots`` (general many-to-many): per probe
  row the match counts (two searches of the build keys), then per output
  slot the match itself (one search of the running match count). Two
  functions so that ``JoinExec`` can read the count between them and
  size the second by it; ``probe_expand`` is the two in one program at a
  static output capacity.

Keys are single int64 columns (dict codes / ints / dates cast to int64).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .search import build_levels, count_below

INT64_SENTINEL = jnp.iinfo(jnp.int64).max


@dataclass
class BuildTable:
    """Build side of a join: always carries the sorted representation,
    which the probes search in steps of 128 (``kernels/search.py``; its
    levels are built inside the probe program, one pass over
    ``sorted_keys``, so the table holds nothing for them); near-dense
    integer keys additionally carry a direct-index table
    (``dense_rows``/``dense_base``) so probes are ONE gather with no
    search at all."""

    sorted_keys: jax.Array  # int64 [Nb] (dead rows = sentinel, at end)
    order: jax.Array  # int32 [Nb] original row index per sorted slot
    num_live: jax.Array  # int32 scalar
    dense_rows: Optional[jax.Array] = None  # int32 [R]: key-base -> row | -1
    dense_base: Optional[jax.Array] = None  # int64 scalar


jax.tree_util.register_dataclass(
    BuildTable,
    data_fields=["sorted_keys", "order", "num_live", "dense_rows",
                 "dense_base"],
    meta_fields=[],
)


def build_lookup(keys: jax.Array, live: jax.Array) -> BuildTable:
    keyed = jnp.where(live, keys, INT64_SENTINEL)
    order = jnp.argsort(keyed, stable=True).astype(jnp.int32)
    return BuildTable(keyed[order], order, jnp.sum(live.astype(jnp.int32)))


def build_dense(keys: jax.Array, live: jax.Array, base: jax.Array,
                size: int) -> Tuple[jax.Array, jax.Array]:
    """Direct-index build: scatter live rows into a [size] table keyed by
    ``key - base``. Returns (dense_rows int32 [size] with -1 = empty,
    has_duplicates bool scalar). ``size`` is static (shape)."""
    n = keys.shape[0]
    idx = (keys - base).astype(jnp.int64)
    # dead rows scatter out of bounds -> dropped
    slot = jnp.where(live, idx, jnp.int64(size)).astype(jnp.int32)
    counts = jnp.zeros((size,), jnp.int32).at[slot].add(
        1, mode="drop")
    rows = jnp.full((size,), -1, jnp.int32).at[slot].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    return rows, jnp.any(counts > 1)


def build_sorted_with_unique(
    keys: jax.Array, live: jax.Array
) -> Tuple[BuildTable, jax.Array]:
    """Sorted build table + a uniqueness flag computed ON DEVICE, so the
    caller fetches one scalar instead of the whole sorted key array."""
    table = build_lookup(keys, live)
    sk = table.sorted_keys
    n = sk.shape[0]
    pos = jnp.arange(1, n, dtype=jnp.int32)
    dup = jnp.any(jnp.logical_and(sk[1:] == sk[:-1], pos < table.num_live))
    return table, jnp.logical_not(dup)


def probe_unique(
    table: BuildTable, probe_keys: jax.Array, probe_live: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Probe assuming unique build keys (FK->PK join).

    Returns (build_row_indices int32 [Np], matched bool [Np]). Unmatched
    probes get index 0 with matched=False; the caller masks them out
    (inner join) or null-fills (left join).
    """
    if table.dense_rows is not None:
        size = table.dense_rows.shape[0]
        idx = probe_keys - table.dense_base
        in_range = jnp.logical_and(idx >= 0, idx < size)
        slot = jnp.clip(idx, 0, size - 1).astype(jnp.int32)
        row = jnp.take(table.dense_rows, slot)
        matched = jnp.logical_and(
            jnp.logical_and(in_range, row >= 0), probe_live)
        return jnp.where(matched, row, 0), matched
    nb = table.sorted_keys.shape[0]
    idx = count_below(build_levels(table.sorted_keys), probe_keys)
    idx = jnp.minimum(idx, nb - 1)
    hit = jnp.equal(table.sorted_keys[idx], probe_keys)
    hit = jnp.logical_and(hit, probe_keys != INT64_SENTINEL)
    matched = jnp.logical_and(hit, probe_live)
    build_rows = jnp.where(matched, table.order[idx], 0)
    return build_rows, matched


def probe_semi(
    table: BuildTable, probe_keys: jax.Array, probe_live: jax.Array
) -> jax.Array:
    """Semi-join mask: probe rows whose key exists in the build side."""
    _, matched = probe_unique(table, probe_keys, probe_live)
    return matched


def probe_counts(table: BuildTable, probe_keys: jax.Array) -> jax.Array:
    """Number of build matches per probe key (for many-to-many planning)."""
    levels = build_levels(table.sorted_keys)
    return (count_below(levels, probe_keys, "right")
            - count_below(levels, probe_keys, "left"))


def probe_ranges(
    table: BuildTable, probe_keys: jax.Array, probe_live: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """First half of the expanding probe, everything that costs a PROBE
    ROW: where each probe key's matches start among the sorted build
    keys, and the running count of matches.

    Returns (lo int32 [Np], ends int32 [Np], total_matches scalar): probe
    row p matches sorted build slots ``lo[p] .. lo[p] + count[p]``, and
    ``ends`` is the inclusive running sum of the counts (dead probe rows
    count 0), so the matches of row p fill output slots
    ``ends[p-1] .. ends[p]``. Nothing here has the output's size: a
    caller that can read ``total`` on the host sizes ``expand_slots`` by
    it.
    """
    keyed = jnp.where(probe_live, probe_keys, INT64_SENTINEL - 1)
    levels = build_levels(table.sorted_keys)
    lo = count_below(levels, keyed, "left")
    counts = count_below(levels, keyed, "right") - lo
    ends = jnp.cumsum(jnp.where(probe_live, counts, 0))
    return lo, ends, ends[-1]


def expand_slots(
    table: BuildTable,
    lo: jax.Array,
    ends: jax.Array,
    total: jax.Array,
    out_capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Second half, everything that costs an OUTPUT SLOT: the matches of
    ``probe_ranges`` written as a prefix of a static output capacity.

    Returns (probe_row_idx [C], build_row_idx [C], out_live [C]); the
    pairs go probe row by probe row, a row's matches in sorted build
    order. Matches past the capacity are cut off.
    """
    C = out_capacity
    out_slot = jnp.arange(C, dtype=jnp.int32)
    # For each output slot, find its probe row: the row whose [offset,
    # offset+count) window contains the slot, so the first whose running
    # count of matches passes the slot.
    probe_of_slot = count_below(build_levels(ends), out_slot, "right")
    probe_of_slot = jnp.minimum(probe_of_slot, ends.shape[0] - 1)
    # a row's window starts where the row before it ended
    offset = jnp.where(probe_of_slot > 0,
                       ends[jnp.maximum(probe_of_slot - 1, 0)], 0)
    build_slot = lo[probe_of_slot] + (out_slot - offset)
    nb = table.sorted_keys.shape[0]
    build_slot = jnp.minimum(build_slot, nb - 1)
    out_live = out_slot < jnp.minimum(total, C)
    build_rows = jnp.where(out_live, table.order[build_slot], 0)
    probe_rows = jnp.where(out_live, probe_of_slot, 0)
    return probe_rows, build_rows, out_live


def probe_expand(
    table: BuildTable,
    probe_keys: jax.Array,
    probe_live: jax.Array,
    out_capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """General inner join with row expansion to a static output
    capacity: ``probe_ranges`` and ``expand_slots`` in ONE program, for
    a caller that cannot read the count in between (the mesh join's SPMD
    program, ``physical/mesh_agg.py``).

    Returns (probe_row_idx [C], build_row_idx [C], out_live [C],
    total_matches scalar). If total_matches > out_capacity the result is
    truncated, and the returned total says so.
    """
    lo, ends, total = probe_ranges(table, probe_keys, probe_live)
    probe_rows, build_rows, out_live = expand_slots(
        table, lo, ends, total, out_capacity)
    return probe_rows, build_rows, out_live, total
