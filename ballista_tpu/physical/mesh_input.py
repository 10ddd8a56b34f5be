"""Device-resident input assembly for mesh-fused stages.

Replaces the round-2 host funnel: fused-stage producers used to execute on
host, get concatenated in numpy, and be re-uploaded per stage
(`np.asarray` of every column). Now producer partitions are executed with
their output pinned round-robin across the mesh devices, laid out into
uniform per-device batches ON DEVICE (dictionary remap + concat + compact
are XLA gathers), and assembled into one sharded global array with
``jax.make_array_from_single_device_arrays`` — data never round-trips
host memory; only per-slot live-row COUNTS (int32 scalars) sync to pick
the uniform capacity.

Chaining: when a fused stage's producer is itself a mesh-fused operator
(or a projection/filter/partial-aggregate pipeline over one), the
producer's stacked per-device output is fed straight into the consumer's
SPMD program — an HBM-resident stage boundary. This is SURVEY §7's
"device-memory partition cache": consecutive fused stages exchange data
over ICI only (reference model being replaced: materialized IPC files +
rust/core/src/execution_plans/shuffle_reader.rs:77-99).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..columnar import (
    Column,
    ColumnBatch,
    Dictionary,
    empty_batch,
)
from ..compile import bucket_capacity, governed
from ..datatypes import Schema
from ..parallel.mesh import shard_map

# Instrumentation (tests assert the device path actually ran; the
# benchmark's mesh readers take the same events from the trace ring):
#   slot_assemblies — producer outputs laid out over the mesh on device
#                     (a ``mesh.assemble`` span, ``how="assembled"``);
#   chained_stages  — stage inputs taken straight from a fused producer's
#                     stacked HBM output (``how="chained"``);
#   exchanges       — sides exchanged over the mesh (``mesh.exchange``).
STATS = {"slot_assemblies": 0, "chained_stages": 0, "exchanges": 0}


def reset_stats() -> None:
    for k in STATS:
        STATS[k] = 0


def note(stat: str, event: str, **attrs) -> None:
    """One mesh event: counted in ``STATS[stat]`` and emitted as a trace
    event (``tracing.span_totals()``, the ring, the profiler's trace)."""
    from ..observability.tracing import trace_event

    STATS[stat] += 1
    trace_event(event, **attrs)


# ---------------------------------------------------------------------------
# dictionary unification (host metadata only; code remap is a device gather)
# ---------------------------------------------------------------------------


def _union_dicts(schema: Schema, batches: List[ColumnBatch]):
    """Per field: one shared dictionary for every batch + per-batch int32
    remap tables (None where codes are already in the shared space).
    Only dictionary VALUES (host metadata) are touched; row data stays on
    device."""
    n_fields = len(schema.fields)
    remaps = [[None] * n_fields for _ in batches]
    dicts: List[Optional[Dictionary]] = []
    for i in range(n_fields):
        ds = [b.columns[i].dictionary for b in batches]
        d0 = next((d for d in ds if d is not None), None)
        if d0 is None:
            dicts.append(None)
            continue
        if all(d is None or d is d0 for d in ds):
            dicts.append(d0)
            continue
        from ..observability import trace_span
        from .. import columnar_registry

        with trace_span("host.dictionary", site="mesh.union",
                        column=schema.fields[i].name, n_dicts=len(ds)):
            # registry: shared-entry dictionaries resolve to the max
            # version + cached int32 remaps (the device gather in
            # _apply_remaps); unregistered fall back to the legacy
            # sorted union inside the registry module
            ud, rms = columnar_registry.unify(ds)
            for bi, r in enumerate(rms):
                if r is not None:
                    remaps[bi][i] = r
        dicts.append(ud)
    return dicts, remaps


def _apply_remaps(schema: Schema, b: ColumnBatch, remap_row, dicts
                  ) -> ColumnBatch:
    """Rebind a batch to the shared dictionaries (device-side code
    gather); also normalizes the schema object so every slot shares one
    pytree aux."""
    cols = []
    for col, r, ud in zip(b.columns, remap_row, dicts):
        d = col.dictionary
        vals = col.values
        if ud is not None:
            if r is not None and d is not ud:
                vals = jnp.take(jnp.asarray(r), vals.astype(jnp.int32),
                                mode="clip")
            d = ud
        cols.append(Column(vals, col.dtype, col.validity, d))
    return ColumnBatch(schema, cols, b.selection, b.num_rows)


# ---------------------------------------------------------------------------
# per-slot layout: compact live rows into a uniform fixed capacity
# ---------------------------------------------------------------------------


def _compact_impl(big: ColumnBatch, cap: int) -> ColumnBatch:
    """Gather live rows to the front of a [cap] batch (validity
    materialized so every slot shares one pytree structure). Traced."""
    from .base import compact_perm

    n = big.capacity
    perm = compact_perm(big.selection, min(cap, n))
    if cap > n:
        perm = jnp.concatenate(
            [perm, jnp.zeros((cap - n,), jnp.int32)]
        )
    live = jnp.arange(cap, dtype=jnp.int32) < big.num_rows
    cols = []
    for col in big.columns:
        vals = jnp.take(col.values, perm)
        validity = (
            jnp.take(col.validity, perm)
            if col.validity is not None
            else jnp.ones((cap,), jnp.bool_)
        )
        cols.append(Column(vals, col.dtype, jnp.logical_and(validity, live),
                           col.dictionary))
    return ColumnBatch(big.schema, cols, live,
                       big.num_rows.astype(jnp.int32))


def _compact_to(big: ColumnBatch, cap: int) -> ColumnBatch:
    """Governed jit of :func:`_compact_impl` at a static capacity."""
    return governed(
        ("mesh.compact_to", cap),
        lambda: partial(_compact_impl, cap=cap),
    )(big)


# ---------------------------------------------------------------------------
# mesh assembly
# ---------------------------------------------------------------------------


def stack_to_mesh(slot_batches: List[ColumnBatch], mesh):
    """Per-device batches -> one stacked ColumnBatch pytree whose leaves
    are [n_dev, ...] arrays sharded over the mesh axis. Each slot's
    leaves are placed on their device (a device-to-device copy when the
    slot was computed elsewhere — ICI, never host) and assembled without
    any global materialization. Single-process alias of
    multihost.stack_local_to_global (where local devices = all)."""
    from ..parallel.multihost import stack_local_to_global

    return stack_local_to_global(slot_batches, mesh)


def assemble_over_mesh(producer, schema: Schema, mesh
                       ) -> Tuple[ColumnBatch, int]:
    """Execute ``producer`` with each partition pinned to a mesh device
    (round-robin) and lay the output over the mesh: per-slot dictionary
    remap + concat + compaction all run as device gathers; only live-row
    counts sync to host. Producers with fewer partitions than devices
    are ROW-split instead (device-side window slices of the compacted
    whole), so a 1-partition dim-table scan doesn't put every row in one
    slot and inflate the uniform capacity n_dev-fold.

    Multi-process (cross-host) meshes: each process executes only the
    partitions of ITS devices' slots and supplies only local shards; the
    uniform capacity is agreed through a replicated global max.
    Correctness requires utf8 dictionaries to be content-identical
    across processes — guaranteed for table scans (table-wide
    dictionaries are built over all partitions of the source, io/text.py).
    Returns (stacked batch, per-device capacity)."""
    from ..parallel import multihost

    devices = list(mesh.devices.flat)
    n_dev = len(devices)
    multi = multihost.is_multiprocess()
    local_ids = {d.id for d in jax.local_devices()}
    local_slots = [i for i, d in enumerate(devices)
                   if not multi or d.id in local_ids]
    nparts = producer.output_partitioning().num_partitions
    row_split = nparts < n_dev
    slots: List[List[ColumnBatch]] = [[] for _ in range(n_dev)]
    for p in range(nparts):
        slot = p % n_dev
        if multi and not row_split and slot not in local_slots:
            continue  # another process owns this slot's device
        if row_split:
            slots[slot].extend(producer.execute(p))
        else:
            with jax.default_device(devices[slot]):
                for b in producer.execute(p):
                    slots[slot].append(b)
    for i in local_slots:
        if not slots[i] and not row_split:
            slots[i].append(empty_batch(schema))

    flat = [b for s in slots for b in s]
    dicts, remap_rows = _union_dicts(schema, flat)

    from .base import concat_batches

    slot_bigs: dict = {}
    i = 0
    for idx in range(n_dev):
        s = slots[idx]
        if not s:
            continue
        rows = remap_rows[i : i + len(s)]
        i += len(s)
        remapped = [
            _apply_remaps(schema, b, r, dicts) for b, r in zip(s, rows)
        ]
        slot_bigs[idx] = (remapped[0] if len(remapped) == 1
                          else concat_batches(schema, remapped))

    if row_split:
        # every process reads the whole (small) producer and slices its
        # local windows — duplicated work, but globally consistent
        bigs = [slot_bigs[k] for k in sorted(slot_bigs)]
        if not bigs:  # producer emitted nothing (e.g. empty MemTable)
            bigs = [empty_batch(schema)]
        big = bigs[0] if len(bigs) == 1 else concat_batches(schema, bigs)
        n = int(big.num_rows)  # scalar sync only
        cap = bucket_capacity(max(-(-n // n_dev), 1))
        packed = _compact_to(big, cap=n_dev * cap)
        slot_batches = [
            _window_slot(packed, d * cap, cap,
                         min(max(n - d * cap, 0), cap))
            for d in local_slots
        ]
        return multihost.stack_local_to_global(slot_batches, mesh), cap

    if multi:
        # capacity must agree across processes: replicated global max
        local_counts = [slot_bigs[i].num_rows for i in local_slots]
        gcounts = multihost.stack_local_to_global(local_counts, mesh)
        cap = bucket_capacity(max(multihost.host_max(gcounts), 1))
    else:
        # ONE batched fetch for all slot counts: sequential int() reads
        # would pay a device->host round-trip per device
        from ..observability.tracing import trace_span

        with trace_span("device.block", site="mesh.input_counts",
                        n=len(local_slots)):
            counts = [int(c) for c in jax.device_get(
                [slot_bigs[i].num_rows for i in local_slots])]
        cap = bucket_capacity(max(max(counts), 1))
    slot_batches = [_compact_to(slot_bigs[i], cap=cap)
                    for i in local_slots]
    return multihost.stack_local_to_global(slot_batches, mesh), cap


def _window_slot(packed: ColumnBatch, start: int, cap: int,
                 count: int) -> ColumnBatch:
    """Rows [start, start+cap) of a front-compacted batch as a slot batch
    (device-side slices; ``count`` live rows at the front)."""
    cols = [
        Column(c.values[start : start + cap], c.dtype,
               (c.validity[start : start + cap]
                if c.validity is not None
                else jnp.ones((cap,), jnp.bool_)),
               c.dictionary)
        for c in packed.columns
    ]
    live = packed.selection[start : start + cap]
    return ColumnBatch(packed.schema, cols, live,
                       jnp.asarray(np.int32(count)))


# ---------------------------------------------------------------------------
# HBM chaining: fused producer -> fused consumer without leaving the mesh
# ---------------------------------------------------------------------------


# mesh.* governed namespaces are LRU-bounded (compile.MESH_NS_CAP):
# their keys hold meshes and pytree structures whose aux-data pins
# identity-hashed per-query Dictionary objects — an unbounded cache
# would pin executables + dictionaries forever
from ..compile import MESH_NS_CAP as _MESH_NS_CAP


def _maybe_compact_stacked(stacked: ColumnBatch, mesh,
                           shrink_factor: int = 4) -> ColumnBatch:
    """Shrink a sparse stacked batch with one per-device SPMD compaction
    (costs a host sync on the [n_dev] live counts — int32s, not data)."""
    from ..parallel.multihost import host_max

    cap = int(stacked.selection.shape[1])
    new_cap = max(bucket_capacity(host_max(stacked.num_rows)), 8)
    if new_cap * shrink_factor > cap:
        return stacked
    axis = mesh.axis_names[0]

    def build():
        @partial(shard_map, mesh=mesh, in_specs=(P(axis),),
                 out_specs=P(axis), check_vma=False)
        def run(st):
            b = jax.tree.map(lambda x: x[0], st)
            out = _compact_impl(b, new_cap)
            return jax.tree.map(lambda x: x[None], out)

        return run

    key = ("mesh.compact", mesh, cap, new_cap, jax.tree.structure(stacked))
    return governed(key, build, cap=_MESH_NS_CAP)(stacked)


def _chain_pipeline(plan, chain, inner: ColumnBatch, mesh) -> ColumnBatch:
    """Apply a fused PipelineOp chain per device over a stacked input."""
    axis = mesh.axis_names[0]

    def build():
        # twins: don't pin the producer subtree in the governed entry
        twins = [op.trace_twin() for op in chain]

        @partial(shard_map, mesh=mesh, in_specs=(P(axis),),
                 out_specs=P(axis), check_vma=False)
        def run(st):
            b = jax.tree.map(lambda x: x[0], st)
            for op in twins:
                b = op.device_transform(b)
            return jax.tree.map(lambda x: x[None], b)

        return run

    key = ("mesh.chain", tuple(op.compile_signature() for op in chain),
           mesh, int(inner.selection.shape[1]))
    return governed(key, build, cap=_MESH_NS_CAP,
                    metrics=plan.metrics())(inner)


def _chain_partial_agg(agg, inner: ColumnBatch, mesh) -> ColumnBatch:
    """Run a partial HashAggregate per device over a stacked input
    (adaptive group capacity with whole-SPMD retry, like the final
    aggregate inside MeshAggExec)."""
    from ..columnar import round_capacity

    axis = mesh.axis_names[0]
    in_cap = int(inner.selection.shape[1])
    cap = agg.group_capacity
    while True:
        fn = agg._get_grouped_fn(cap, in_cap)

        def build():
            @partial(shard_map, mesh=mesh, in_specs=(P(axis),),
                     out_specs=(P(axis), P(axis)), check_vma=False)
            def run(st):
                b = jax.tree.map(lambda x: x[0], st)
                out, ng = fn(b)
                return jax.tree.map(lambda x: x[None], out), ng[None]

            return run

        key = ("mesh.partial_agg", agg.compile_signature(), mesh, in_cap,
               cap)
        out_stacked, ngs = governed(key, build, cap=_MESH_NS_CAP,
                                    metrics=agg.metrics())(inner)
        from ..parallel.multihost import host_max

        ng = host_max(ngs)  # multihost-safe replicated max
        if ng <= cap:
            return out_stacked
        cap = round_capacity(ng)


def _try_chain(plan, mesh) -> Optional[ColumnBatch]:
    """Stacked per-device output for plans rooted in a mesh-fused
    operator (possibly under projection/filter/partial-agg wrappers), or
    None when the plan must be assembled from host-driven partitions."""
    from .aggregate import HashAggregateExec
    from .base import PipelineOp
    from .mesh_agg import MeshAggExec, MeshJoinExec

    n_dev = mesh.devices.size
    if isinstance(plan, (MeshAggExec, MeshJoinExec)):
        if plan.n_devices != n_dev:
            return None
        return plan.execute_stacked(mesh)
    if isinstance(plan, PipelineOp):
        chain, source = plan._pipeline_chain()
        inner = _try_chain(source, mesh)
        if inner is None:
            return None
        return _chain_pipeline(plan, chain, inner, mesh)
    if isinstance(plan, HashAggregateExec) and plan.mode == "partial" \
            and plan.group_exprs:
        inner = _try_chain(plan.child, mesh)
        if inner is None:
            return None
        return _chain_partial_agg(plan, inner, mesh)
    return None


def stacked_input(producer, schema: Schema, mesh) -> Tuple[ColumnBatch, int]:
    """The mesh-fused operator input contract: ``producer``'s rows as a
    stacked [n_dev, cap] ColumnBatch sharded over the mesh, + cap.
    Chains HBM-resident when the producer is itself mesh-fused; never
    round-trips row data through host either way. One ``mesh.assemble``
    span an input, with its per-device ``capacity``."""
    from ..observability.tracing import trace_span

    with trace_span("mesh.assemble", n_dev=int(mesh.devices.size)) as span:
        chained = _try_chain(producer, mesh)
        if chained is not None:
            STATS["chained_stages"] += 1
            chained = _maybe_compact_stacked(chained, mesh)
            stacked, cap = chained, int(chained.selection.shape[1])
        else:
            STATS["slot_assemblies"] += 1
            stacked, cap = assemble_over_mesh(producer, schema, mesh)
        span.attrs.update(how="assembled" if chained is None else "chained",
                          capacity=cap)
    return stacked, cap
