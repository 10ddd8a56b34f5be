"""ICI all_to_all hash shuffle: the on-device replacement for the host
shuffle (reference moves whole partitions over Arrow Flight,
rust/core/src/execution_plans/shuffle_reader.rs:77-99; within a TPU slice
we exchange rows over ICI instead).

Works inside ``shard_map`` with static shapes:

1. each device computes a destination id per live row (splitmix64 hash of
   the key mod n_devices);
2. the pack: for each destination the rows bound for it are FOUND, in
   their order, by searching the running count of such rows
   (``kernels/search.py`` ``first_live``, the search of compaction and of
   the join probes), and every column is GATHERED through those indices
   into its send buffer [n_dev, dest_capacity]. No sort and no scatter:
   the chip scatters an element at a time, dead ones too, and a
   ``lax.sort`` over a capacity takes minutes to compile (PERF.md, PRs 22
   and 29). Boolean columns (validity planes) travel as the bits of one
   int32 word a row;
3. one ``lax.all_to_all`` a column exchanges the buffers;
4. per-source row counts travel alongside: what a device receives is
   ``n_dev`` runs of ``dest_capacity`` slots, each live at its front, and
   the counts give the live mask of its [n_dev * dest_capacity] rows.

``dest_capacity`` bounds the rows one device sends to one destination.
The caller sizes it from the counts themselves (``destination_counts``,
read once before the exchange: ``physical/mesh_agg.py``
``exchange_slots``), so a device receives about the live rows bound for
it, not ``n_dev`` whole input capacities. Rows past ``dest_capacity`` are
not sent; the returned counts say so (``max(send_counts) >
dest_capacity``) and the caller runs again with more slots.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .hashing import hash_partition_ids
from .search import first_live

_WORD_BITS = 32  # boolean columns that share one exchanged word


def destination_ids(keys: jax.Array, live: jax.Array, n_dev: int) -> jax.Array:
    """int32 destination device per row (dead rows -> 0). Shares the
    partitioning hash with the host shuffle (hashing.hash_partition_ids) so
    both planes always agree on row destinations."""
    return jnp.where(live, hash_partition_ids(keys.astype(jnp.int64), n_dev), 0)


def destination_counts(live: jax.Array, dest: jax.Array,
                       n_dev: int) -> jax.Array:
    """int32 [n_dev]: the live rows of this device bound for each
    destination."""
    bound = jnp.logical_and(
        dest[None, :] == jnp.arange(n_dev, dtype=dest.dtype)[:, None],
        live[None, :])
    return jnp.sum(bound, axis=1, dtype=jnp.int32)


def _pack_bools(columns: Sequence[jax.Array]) -> jax.Array:
    word = jnp.zeros(columns[0].shape, jnp.int32)
    for bit, col in enumerate(columns):
        word = word | (col.astype(jnp.int32) << bit)
    return word


def all_to_all_rows(
    columns: Sequence[jax.Array],  # each [N] per-device rows
    live: jax.Array,  # bool [N]
    dest: jax.Array,  # int32 [N] in [0, n_dev)
    axis_name: str,
    n_dev: int,
    dest_capacity: int,
) -> Tuple[List[jax.Array], jax.Array, jax.Array]:
    """Exchange rows so each lands on its destination device, in the
    order it had at its source.

    Returns (out_columns each [n_dev*dest_capacity], out_live, send_counts
    [n_dev] — callers check max(send_counts) <= dest_capacity for overflow).
    """
    counts = destination_counts(live, dest, n_dev)

    def bound_for(d):
        return first_live(jnp.logical_and(live, dest == d), dest_capacity)

    # [n_dev * dest_capacity] source row of every send slot (0 past a
    # destination's count: sent, and dead on arrival)
    take = lax.map(bound_for,
                   jnp.arange(n_dev, dtype=dest.dtype)).reshape(-1)

    def exchange(col):
        got = lax.all_to_all(
            jnp.take(col, take).reshape(n_dev, dest_capacity),
            axis_name, 0, 0, tiled=False)
        return got.reshape(n_dev * dest_capacity)

    bools = [i for i, c in enumerate(columns) if c.dtype == jnp.bool_]
    out_cols: List[jax.Array] = [None] * len(columns)
    for i, col in enumerate(columns):
        if col.dtype != jnp.bool_:
            out_cols[i] = exchange(col)
    for at in range(0, len(bools), _WORD_BITS):
        group = bools[at:at + _WORD_BITS]
        word = exchange(_pack_bools([columns[i] for i in group]))
        for bit, i in enumerate(group):
            out_cols[i] = ((word >> bit) & 1).astype(jnp.bool_)

    # counts destined to me, from each source device
    my_counts = lax.all_to_all(
        jnp.minimum(counts, dest_capacity).reshape(n_dev, 1),
        axis_name, 0, 0, tiled=False,
    ).reshape(n_dev)
    out_live = (jnp.arange(dest_capacity, dtype=jnp.int32)[None, :]
                < my_counts[:, None]).reshape(n_dev * dest_capacity)
    return out_cols, out_live, counts
