"""Grouped and ungrouped aggregation kernels.

TPU-native replacement for the reference's ``HashAggregateExec`` (reference:
rust/core/proto/ballista.proto:370-384; planner splits it into
Partial->shuffle->Final at rust/scheduler/src/planner.rs:149-171 — our
physical operators follow the same two-phase decomposition).

A CPU hash table is hostile to XLA, so grouping is *sort-based*:

1. rows are ordered by ONE multi-operand ``lax.sort`` (lexicographic over
   [dead-flag, key columns..., row-index payload]; no bit-packing, so any
   number/width of key columns works), sinking dead rows to the end;
2. run-boundary detection (ANY key differs from the predecessor) + a prefix
   sum assigns dense group ids;
3. ``segment_sum/min/max`` with ``indices_are_sorted=True`` reduces each
   aggregate in one pass.

SQL semantics carried through:
- NULL group keys form their own group (each key column contributes its
  validity as an implicit sort/boundary key);
- NULL inputs are excluded from aggregates, and each aggregate reports a
  per-group validity ("any non-NULL input seen"), so all-NULL groups yield
  NULL rather than the reduction identity.

Everything is static-shaped: the caller supplies ``group_capacity`` and gets
fixed-size outputs plus a ``group_valid`` mask; ``num_groups`` reports the
TRUE group count so callers can detect overflow and retry with a larger
capacity. Sums over decimals stay in int64, so results are exact (TPU f64
is avoided entirely).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..errors import ExecutionError


# ---------------------------------------------------------------------------
# Grouped aggregation
# ---------------------------------------------------------------------------


def _run_boundaries(cols: Sequence[jax.Array]) -> jax.Array:
    """bool [N]: row i starts a new run of the (sorted) key columns —
    ANY column differs from its predecessor (row 0 always starts one).
    Shared by the sort-based grouping and the distinct-count kernel so
    their byte-identical ordering contract stays in lockstep."""
    first = None
    for ks in cols:
        diff = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), ks[1:] != ks[:-1]])
        first = diff if first is None else jnp.logical_or(first, diff)
    return first


@dataclass
class AggInput:
    """One aggregate to compute: op in {sum, count, min, max}."""

    op: str
    values: Optional[jax.Array]  # None for count(*)
    validity: Optional[jax.Array]  # None = all valid


@dataclass
class GroupedResult:
    rep_indices: jax.Array  # int32 [G] original row index of each group's first row
    group_valid: jax.Array  # bool [G]
    num_groups: jax.Array  # int32 scalar (TRUE count; may exceed capacity G)
    aggregates: List[jax.Array]  # each [G]
    agg_valid: List[jax.Array]  # bool [G] per aggregate ("any input seen")


jax.tree_util.register_dataclass(
    GroupedResult,
    data_fields=["rep_indices", "group_valid", "num_groups", "aggregates",
                 "agg_valid"],
    meta_fields=[],
)


def grouped_aggregate(
    keys: Sequence[jax.Array],  # one or more [N] key columns (ints/codes)
    live: jax.Array,  # bool [N] live-row mask
    aggs: Sequence[AggInput],
    group_capacity: int,
    key_validities: Optional[Sequence[Optional[jax.Array]]] = None,
) -> GroupedResult:
    keys = list(keys)
    if not keys:
        raise ExecutionError("grouped_aggregate requires at least one key")
    if key_validities is None:
        key_validities = [None] * len(keys)
    # NULL keys group together: each nullable key contributes (validity,
    # value-or-0) as the effective sort/boundary pair
    eff_keys: List[jax.Array] = []
    for k, kv in zip(keys, key_validities):
        if kv is not None:
            eff_keys.append(kv.astype(jnp.int32))
            eff_keys.append(jnp.where(kv, k, jnp.zeros((), k.dtype)))
        else:
            eff_keys.append(k)

    n = live.shape[0]
    # ONE multi-operand lexicographic sort (dead flag first, then keys,
    # then the row index as payload) replaces K chained stable argsorts +
    # per-key gathers: a single lax.sort is both cheaper to trace and the
    # form XLA lowers best on TPU. Sorted keys fall out as byproducts, so
    # boundary detection needs no extra gathers either.
    dead = jnp.logical_not(live)
    idx = jnp.arange(n, dtype=jnp.int32)
    if len(eff_keys) == 1:
        # PRESORTED fast path (runtime-branched, no host sync): group-by
        # over a clustered key (TPC-H q18's l_orderkey — file order) can
        # skip the O(N log N) sort entirely when the key is already
        # non-decreasing over a contiguous live prefix. lax.cond executes
        # only the taken branch, so unsorted inputs pay one O(N) check.
        k0 = eff_keys[0]
        live_prefix = jnp.all(live[1:] <= live[:-1])  # no live after dead
        nondecreasing = jnp.all(
            jnp.logical_or(k0[1:] >= k0[:-1], jnp.logical_not(live[1:]))
        )
        presorted = jnp.logical_and(live_prefix, nondecreasing)

        def _fast(_):
            return idx, (k0,), live

        def _slow(_):
            ops = jax.lax.sort((dead, k0, idx), num_keys=2, is_stable=True)
            return ops[-1], (ops[1],), jnp.logical_not(ops[0])

        order, sorted_keys, live_sorted = jax.lax.cond(
            presorted, _fast, _slow, None)
    else:
        sorted_ops = jax.lax.sort(
            (dead, *eff_keys, idx), num_keys=1 + len(eff_keys),
            is_stable=True
        )
        order = sorted_ops[-1]
        sorted_keys = sorted_ops[1:-1]
        live_sorted = jnp.logical_not(sorted_ops[0])

    # a row starts a new group if live and ANY key differs from predecessor
    starts = jnp.logical_and(_run_boundaries(sorted_keys), live_sorted)
    gid = jnp.cumsum(starts.astype(jnp.int32)) - 1  # [-1..G-1]
    num_groups = jnp.sum(starts.astype(jnp.int32))
    # dead rows / overflow go to the trash segment group_capacity
    seg = jnp.where(live_sorted, jnp.minimum(gid, group_capacity), group_capacity)

    G = group_capacity

    # representative original-row index per group (first member in sort order)
    pos = jnp.arange(n, dtype=jnp.int32)
    first_pos = jax.ops.segment_min(
        jnp.where(live_sorted, pos, n), seg, num_segments=G + 1,
        indices_are_sorted=True,
    )[:G]
    safe_first = jnp.minimum(first_pos, n - 1)
    rep_indices = order[safe_first].astype(jnp.int32)

    group_valid = jnp.arange(G, dtype=jnp.int32) < num_groups

    results: List[jax.Array] = []
    valid_results: List[jax.Array] = []
    for a in aggs:
        valid = a.validity[order] if a.validity is not None else None
        if a.op == "count":
            v = jnp.ones((n,), jnp.int64)
            if valid is not None:
                v = jnp.where(valid, v, 0)
            r = jax.ops.segment_sum(v, seg, num_segments=G + 1,
                                    indices_are_sorted=True)[:G]
            va = group_valid
        else:
            if a.values is None:
                raise ExecutionError(f"{a.op} requires input values")
            v = a.values[order]
            if a.op == "sum":
                if valid is not None:
                    v = jnp.where(valid, v, jnp.zeros((), v.dtype))
                r = jax.ops.segment_sum(v, seg, num_segments=G + 1,
                                        indices_are_sorted=True)[:G]
            elif a.op == "min":
                if valid is not None:
                    v = jnp.where(valid, v, _max_ident(v.dtype))
                r = jax.ops.segment_min(v, seg, num_segments=G + 1,
                                        indices_are_sorted=True)[:G]
            elif a.op == "max":
                if valid is not None:
                    v = jnp.where(valid, v, _min_ident(v.dtype))
                r = jax.ops.segment_max(v, seg, num_segments=G + 1,
                                        indices_are_sorted=True)[:G]
            else:
                raise ExecutionError(f"unknown aggregate op {a.op}")
            if valid is not None:
                seen = jax.ops.segment_max(
                    valid.astype(jnp.int32), seg, num_segments=G + 1,
                    indices_are_sorted=True,
                )[:G]
                va = jnp.logical_and(group_valid, seen > 0)
            else:
                va = group_valid
        results.append(jnp.where(va, r, jnp.zeros((), r.dtype)))
        valid_results.append(va)

    return GroupedResult(rep_indices, group_valid, num_groups, results,
                         valid_results)


def grouped_distinct_count(
    group_keys: Sequence[jax.Array],  # [N] key columns (ints/codes)
    live: jax.Array,  # bool [N] live-row mask
    distinct_key: jax.Array,  # [N] the COUNT(DISTINCT x) column
    group_capacity: int,
    group_validities: Optional[Sequence[Optional[jax.Array]]] = None,
    distinct_validity: Optional[jax.Array] = None,
) -> GroupedResult:
    """Single-pass COUNT(DISTINCT x) GROUP BY g1..gk.

    The SQL planner rewrites COUNT(DISTINCT) into a two-level aggregate
    (dedup on (g, x), then count per g) — three sort-based groupings over
    the same rows. This kernel needs ONE lexicographic sort over
    [dead, g.., x, idx]: a row opens a *group* when any g-key differs
    from its predecessor, and opens a *distinct pair* when additionally x
    differs — the per-group pair-start count IS the distinct count.

    SQL semantics match the two-level rewrite exactly: NULL group keys
    form their own group (validity rides the sort key), NULL x values
    are never counted (but a group whose every x is NULL still appears,
    with count 0). Input duplicates are fine — only pair boundaries
    count. Output group order equals ``grouped_aggregate``'s (sorted by
    the effective key encoding), so swapping the rewrite for this kernel
    is byte-identical. Result carries one aggregate: the int64 counts.
    """
    group_keys = list(group_keys)
    if not group_keys:
        raise ExecutionError("grouped_distinct_count requires a group key")
    if group_validities is None:
        group_validities = [None] * len(group_keys)
    eff_g: List[jax.Array] = []
    for k, kv in zip(group_keys, group_validities):
        if kv is not None:
            eff_g.append(kv.astype(jnp.int32))
            eff_g.append(jnp.where(kv, k, jnp.zeros((), k.dtype)))
        else:
            eff_g.append(k)
    eff_d: List[jax.Array] = []
    if distinct_validity is not None:
        eff_d.append(distinct_validity.astype(jnp.int32))
        eff_d.append(jnp.where(distinct_validity, distinct_key,
                               jnp.zeros((), distinct_key.dtype)))
    else:
        eff_d.append(distinct_key)

    n = live.shape[0]
    dead = jnp.logical_not(live)
    idx = jnp.arange(n, dtype=jnp.int32)
    ops = jax.lax.sort((dead, *eff_g, *eff_d, idx),
                       num_keys=1 + len(eff_g) + len(eff_d),
                       is_stable=True)
    order = ops[-1]
    live_sorted = jnp.logical_not(ops[0])
    sg = ops[1:1 + len(eff_g)]
    sd = ops[1 + len(eff_g):-1]

    g_first = _run_boundaries(sg)
    pair_first = jnp.logical_or(g_first, _run_boundaries(sd))
    starts = jnp.logical_and(g_first, live_sorted)
    gid = jnp.cumsum(starts.astype(jnp.int32)) - 1
    num_groups = jnp.sum(starts.astype(jnp.int32))
    G = group_capacity
    seg = jnp.where(live_sorted, jnp.minimum(gid, G), G)

    # pairs whose x is NULL exist as groups' rows but never count
    counted = jnp.logical_and(pair_first, live_sorted)
    if distinct_validity is not None:
        counted = jnp.logical_and(counted, distinct_validity[order])
    counts = jax.ops.segment_sum(
        counted.astype(jnp.int64), seg, num_segments=G + 1,
        indices_are_sorted=True)[:G]

    pos = jnp.arange(n, dtype=jnp.int32)
    first_pos = jax.ops.segment_min(
        jnp.where(live_sorted, pos, n), seg, num_segments=G + 1,
        indices_are_sorted=True,
    )[:G]
    rep_indices = order[jnp.minimum(first_pos, n - 1)].astype(jnp.int32)
    group_valid = jnp.arange(G, dtype=jnp.int32) < num_groups
    counts = jnp.where(group_valid, counts, jnp.zeros((), counts.dtype))
    return GroupedResult(rep_indices, group_valid, num_groups, [counts],
                         [group_valid])


def _max_ident(dt):
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.iinfo(dt).max
    return jnp.asarray(jnp.inf, dt)


def _min_ident(dt):
    if jnp.issubdtype(dt, jnp.integer):
        return jnp.iinfo(dt).min
    return jnp.asarray(-jnp.inf, dt)


# ---------------------------------------------------------------------------
# Dense grouping fast path: group ids already small dense ints (dictionary
# codes / booleans with known cardinality). No sort — one fused masked
# reduction per aggregate, which is the MXU/VPU-friendly shape for TPC-H
# q1-style tiny-cardinality GROUP BYs.
# ---------------------------------------------------------------------------


def dense_grouped_aggregate(
    gids: jax.Array,  # int32 [N] in [0, num_groups)
    live: jax.Array,  # bool [N]
    aggs: Sequence[AggInput],
    num_groups: int,
) -> GroupedResult:
    n = gids.shape[0]
    groups = jnp.arange(num_groups, dtype=jnp.int32)
    # [N, G] membership mask, fused into each reduction (never materialized
    # at full width for small G)
    member = jnp.logical_and(live[:, None], gids[:, None] == groups[None, :])

    group_valid = jnp.any(member, axis=0)
    # argmax returns the FIRST True row per group
    rep_indices = jnp.argmax(member, axis=0).astype(jnp.int32)
    num_present = jnp.sum(group_valid.astype(jnp.int32))

    results: List[jax.Array] = []
    valid_results: List[jax.Array] = []
    for a in aggs:
        m = member
        if a.validity is not None:
            m = jnp.logical_and(m, a.validity[:, None])
        if a.op == "count":
            r = jnp.sum(m.astype(jnp.int64), axis=0)
            va = group_valid
        else:
            if a.values is None:
                raise ExecutionError(f"{a.op} requires input values")
            v = a.values[:, None]
            if a.op == "sum":
                r = jnp.sum(jnp.where(m, v, jnp.zeros((), v.dtype)), axis=0)
            elif a.op == "min":
                r = jnp.min(jnp.where(m, v, _max_ident(v.dtype)), axis=0)
            elif a.op == "max":
                r = jnp.max(jnp.where(m, v, _min_ident(v.dtype)), axis=0)
            else:
                raise ExecutionError(f"unknown aggregate op {a.op}")
            va = jnp.any(m, axis=0)
        results.append(jnp.where(va, r, jnp.zeros((), r.dtype)))
        valid_results.append(va)

    return GroupedResult(rep_indices, group_valid, num_present, results,
                         valid_results)


def dense_grouped_scatter(
    gids: jax.Array,  # int32 [N] in [0, num_groups)
    live: jax.Array,  # bool [N]
    aggs: Sequence[AggInput],
    num_groups: int,
) -> GroupedResult:
    """O(N) scatter-based dense grouping for group counts where
    ``dense_grouped_aggregate``'s [N, G] membership product is prohibitive
    (ranged-integer keys: thousands to millions of groups). Same
    semantics: non-compact groups, ``group_valid`` marks occupancy,
    per-aggregate validity is "any non-NULL input seen"."""
    n = gids.shape[0]
    G = num_groups
    slot = jnp.where(live, gids, G).astype(jnp.int32)  # dead -> dropped
    rows = jnp.arange(n, dtype=jnp.int32)
    first = jnp.full((G,), n, jnp.int32).at[slot].min(rows, mode="drop")
    group_valid = first < n
    rep_indices = jnp.minimum(first, n - 1)
    num_present = jnp.sum(group_valid.astype(jnp.int32))

    results: List[jax.Array] = []
    valid_results: List[jax.Array] = []
    for a in aggs:
        valid = a.validity
        if a.op == "count":
            v = jnp.ones((n,), jnp.int64)
            if valid is not None:
                v = jnp.where(valid, v, 0)
            r = jnp.zeros((G,), jnp.int64).at[slot].add(v, mode="drop")
            va = group_valid
        else:
            if a.values is None:
                raise ExecutionError(f"{a.op} requires input values")
            v = a.values
            if a.op == "sum":
                if valid is not None:
                    v = jnp.where(valid, v, jnp.zeros((), v.dtype))
                r = jnp.zeros((G,), v.dtype).at[slot].add(v, mode="drop")
            elif a.op == "min":
                if valid is not None:
                    v = jnp.where(valid, v, _max_ident(v.dtype))
                r = jnp.full((G,), _max_ident(v.dtype), v.dtype) \
                    .at[slot].min(v, mode="drop")
            elif a.op == "max":
                if valid is not None:
                    v = jnp.where(valid, v, _min_ident(v.dtype))
                r = jnp.full((G,), _min_ident(v.dtype), v.dtype) \
                    .at[slot].max(v, mode="drop")
            else:
                raise ExecutionError(f"unknown aggregate op {a.op}")
            if valid is not None:
                seen = jnp.zeros((G,), jnp.int32).at[slot].max(
                    valid.astype(jnp.int32), mode="drop")
                va = jnp.logical_and(group_valid, seen > 0)
            else:
                va = group_valid
        results.append(jnp.where(va, r, jnp.zeros((), r.dtype)))
        valid_results.append(va)

    return GroupedResult(rep_indices, group_valid, num_present, results,
                         valid_results)


# ---------------------------------------------------------------------------
# Ungrouped aggregation (whole-batch reductions)
# ---------------------------------------------------------------------------


def scalar_aggregate(
    live: jax.Array, aggs: Sequence[AggInput]
) -> Tuple[List[jax.Array], List[jax.Array]]:
    """Returns (values, validities) — validity False when no valid input."""
    out: List[jax.Array] = []
    valid_out: List[jax.Array] = []
    for a in aggs:
        valid = live
        if a.validity is not None:
            valid = jnp.logical_and(valid, a.validity)
        any_valid = jnp.any(valid)
        if a.op == "count":
            out.append(jnp.sum(valid.astype(jnp.int64)))
            valid_out.append(jnp.ones((), jnp.bool_))
            continue
        v = a.values
        if a.op == "sum":
            r = jnp.sum(jnp.where(valid, v, jnp.zeros((), v.dtype)))
        elif a.op == "min":
            r = jnp.min(jnp.where(valid, v, _max_ident(v.dtype)))
        elif a.op == "max":
            r = jnp.max(jnp.where(valid, v, _min_ident(v.dtype)))
        else:
            raise ExecutionError(f"unknown aggregate op {a.op}")
        out.append(jnp.where(any_valid, r, jnp.zeros((), r.dtype)))
        valid_out.append(any_valid)
    return out, valid_out


# ---------------------------------------------------------------------------
# Exact fixed-point average: sum/count scaled to 10^6 without overflowing
# ---------------------------------------------------------------------------


def avg_fixed(sum_: jax.Array, count: jax.Array, in_scale: int) -> jax.Array:
    """(sum / count) scaled to Decimal(6), overflow-safe.

    Splits the division: A = q*M + (r*M)//count with q=sum//count,
    r=sum%count, M=10^(6-in_scale) — r*M stays < count*M so the only
    overflow left is a logical |avg| >= ~9.2e12, documented out of range.
    """
    s = sum_.astype(jnp.int64)
    if in_scale > 6:
        s = jax.lax.div(s, jnp.int64(10 ** (in_scale - 6)))
        in_scale = 6
    m = jnp.int64(10 ** (6 - in_scale))
    c = jnp.maximum(count.astype(jnp.int64), 1)
    q = jax.lax.div(s, c)
    r = jax.lax.rem(s, c)
    return q * m + jax.lax.div(r * m, c)
