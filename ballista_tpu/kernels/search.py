"""Search of a sorted vector in steps of 128.

``jnp.searchsorted`` halves its range once a step, and every step is a
gather of ONE element that waits for the step before: log2(n) dependent
single-element gathers a query. The chip gathers a whole ROW of 128
int32 in less time than one element (PERF.md, PR 29 and PR 36, have the
chip tables), so the search here descends in steps of ``BLOCK``: above
the sorted vector stands the last entry of every ``BLOCK`` of it, and
again above that, until a level is short enough (``TOP``) to compare
whole; a query walks down from there, one gathered row a level (two
levels under the top one at 2**21 entries). Queries go ``QUERY_CHUNK``
at a time so that the gathered rows stay tens of MiB.

The chip's lanes are 32-bit, so a 64-bit integer is searched as two
int32 planes, the signed high half and the low half with its top bit
flipped (which makes signed order on the lanes the halves' unsigned
order): exact for every int64, negative, sentinel or packed. One path
for every key and every size: a vector of at most ``TOP`` entries is
compared whole with no level.

``first_live`` is the other half of a compaction: the k-th live row of
a mask is the first whose running count reaches k, so it is searched
for, with no scatter and no sort.

Callers: ``physical/base.py`` ``compact_perm`` (``first_live`` as it
stands), the probes of ``kernels/join.py`` (the sorted build keys and
the running count of matches) and the pack of
``kernels/mesh_shuffle.py`` (``first_live`` once a destination).
"""

from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

BLOCK = 128
TOP = 256
QUERY_CHUNK = 1 << 16

_Planes = Tuple[jax.Array, ...]


class SortedLevels(NamedTuple):
    """A sorted vector ready to be searched: ``top`` compared whole,
    then ``levels`` (rows of ``BLOCK`` entries, finest LAST) walked one
    gathered row each; every entry as its int32 planes."""

    size: int
    top: _Planes
    levels: List[_Planes]


def depth(size: int) -> int:
    """How many levels a query walks under the top one."""
    levels = 0
    while size > TOP:
        size, levels = -(-size // BLOCK), levels + 1
    return levels


def _planes(v: jax.Array) -> _Planes:
    """``v`` as int32 planes whose lexicographic signed order is v's."""
    if v.dtype == jnp.int64:
        low = v.astype(jnp.int32) ^ jnp.int32(-(1 << 31))
        return (v >> 32).astype(jnp.int32), low
    if v.dtype != jnp.int32:
        raise TypeError(f"stepped search over {v.dtype}: int32 or int64")
    return (v,)


def _count_before(entries: _Planes, q: _Planes, right: bool) -> jax.Array:
    """Per query (axis 0) how many of its row of entries (axis 1) sort
    before it (``right``: before or equal to it)."""
    before = entries[-1] <= q[-1] if right else entries[-1] < q[-1]
    for e, x in zip(entries[-2::-1], q[-2::-1]):  # lexicographic
        before = jnp.logical_or(e < x, jnp.logical_and(e == x, before))
    return jnp.sum(before, axis=1, dtype=jnp.int32)


def build_levels(sorted_vec: jax.Array) -> SortedLevels:
    """The levels above a sorted int32 or int64 vector: one pass over
    it. Traced, inside the program that searches it."""
    top, levels = _planes(sorted_vec), []
    while top[0].shape[0] > TOP:
        # edge padding keeps a level sorted; only a query past the last
        # entry can land in it, and count_below cuts that to the size
        rows = tuple(
            jnp.pad(p, (0, -p.shape[0] % BLOCK), mode="edge")
            .reshape(-1, BLOCK) for p in top)
        levels.append(rows)
        top = tuple(r[:, -1] for r in rows)
    return SortedLevels(sorted_vec.shape[0], top, levels[::-1])


def count_below(sorted_levels: SortedLevels, queries: jax.Array,
                side: str = "left") -> jax.Array:
    """For each query how many entries sort before it (``side="left"``)
    or before or equal to it (``"right"``): int32, element for element
    ``jnp.searchsorted(sorted_vec, queries, side=side)``."""
    size, top, levels = sorted_levels
    right = side == "right"

    def descend(chunk):
        q = tuple(p[:, None] for p in _planes(chunk))
        at = _count_before(tuple(p[None, :] for p in top), q, right)
        for rows in levels:
            # a query past the last entry walks down the last block
            at = jnp.minimum(at, rows[0].shape[0] - 1)
            # r[at] with a vector of row numbers: the one form of gather
            # the chip does a whole row at a time
            at = at * BLOCK + _count_before(
                tuple(r[at] for r in rows), q, right)
        return jnp.minimum(at, size)

    n = queries.shape[0]
    if n <= QUERY_CHUNK:
        return descend(queries)
    chunks = jnp.pad(queries, (0, -n % QUERY_CHUNK)).reshape(-1, QUERY_CHUNK)
    return jax.lax.map(descend, chunks).reshape(-1)[:n]


# The running count is taken COUNT_ROW rows at a time because XLA's TPU
# compiler spends 15-30 s on a one-pass cumsum over 2**20 rows and under
# a second on the two-level one, at the same speed (PERF.md, PR 29).
COUNT_ROW = 4096


def running_count(selection: jax.Array) -> jax.Array:
    """How many live rows there are up to and including each row (int32:
    the chip's lanes are 32-bit and a capacity fits)."""
    n = selection.shape[0]
    live = selection.astype(jnp.int32)
    if n <= COUNT_ROW:
        return jnp.cumsum(live, dtype=jnp.int32)
    rows = jnp.pad(live, (0, -n % COUNT_ROW)).reshape(-1, COUNT_ROW)
    within = jnp.cumsum(rows, axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(within[:, -1], dtype=jnp.int32)
    return (within + (ends - within[:, -1])[:, None]).reshape(-1)[:n]


def first_live(selection: jax.Array, size: int) -> jax.Array:
    """Indices of the first ``size`` live rows, in order (0 where there
    are fewer): the gather permutation of a stable front-compaction,
    element for element ``jnp.nonzero(selection, size=size,
    fill_value=0)[0]``. Traced.

    The k-th live row is the first whose running count reaches k, so it
    is SEARCHED for, for k = 1..size: one pass over the capacity plus
    ``size`` row gathers a level (two levels under the top one at 2**20
    rows), so the cost follows the rows kept. There is no scatter
    (``jnp.nonzero`` sends one update for EVERY row of the capacity,
    dead ones too, and the chip scatters an element at a time: 72 ms at
    2**20 rows whatever survives) and no lax.sort."""
    count = running_count(selection)
    kth = jnp.arange(1, size + 1, dtype=jnp.int32)
    return jnp.where(kth <= count[-1],
                     count_below(build_levels(count), kth), 0)
