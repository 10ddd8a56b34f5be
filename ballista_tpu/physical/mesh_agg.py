"""Mesh-fused shuffle aggregation: the ICI fast path for a
Repartition(hash) -> HashAggregate(final) stage pair.

When one executor owns a whole device mesh, materializing N^2 shuffle
files through the host data plane (reference model:
rust/core/src/execution_plans/shuffle_reader.rs:77-99 — whole partitions
over Arrow Flight) wastes the interconnect. This operator runs the pair
as ONE SPMD XLA program instead:

  per device: partial state rows -> hash destination ids
           -> lax.all_to_all row exchange  (kernels.mesh_shuffle)
           -> per-device final aggregation (groups are now co-located)

The row->destination hash is ``compute_partition_ids`` — the same
function the host shuffle uses — so the mesh path and the file path
always agree on row placement (utf8 keys hash their string values via
dictionary stable hashes, never producer-local codes).

The scheduler's fusion pass (distributed/scheduler.py) builds this node
from a shuffle stage + its final-aggregate consumer when the target
executor reports enough devices; ``mesh.devices`` gates it.
"""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..columnar import Column, ColumnBatch, round_capacity
from ..compile import bucket_capacity
from ..datatypes import Schema
from ..errors import ExecutionError
from .. import expr as ex
from ..kernels import mesh_shuffle
from ..kernels.expr_eval import Evaluator
from ..parallel.mesh import make_mesh
from .aggregate import DEFAULT_GROUP_CAPACITY, HashAggregateExec
from .base import PhysicalPlan, Partitioning




def _shuffle_side(b: ColumnBatch, hash_exprs, ev: Evaluator, n_dev: int,
                  slots: int, axis: str = "data") -> ColumnBatch:
    """Traced: hash rows by ``hash_exprs`` and exchange them over the
    mesh axis, ``slots`` a destination; returns the post-shuffle
    per-device batch (capacity n_dev * slots)."""
    dest = _partition_ids(b, hash_exprs, n_dev, ev)
    arrays = [c.values for c in b.columns] + [c.validity for c in b.columns]
    out_arrays, out_live, _counts = mesh_shuffle.all_to_all_rows(
        arrays, b.selection, dest, axis, n_dev, dest_capacity=slots,
    )
    nf = len(b.schema.fields)
    cols = [
        Column(v, f.dtype, va, c.dictionary)
        for v, va, f, c in zip(out_arrays[:nf], out_arrays[nf:],
                               b.schema.fields, b.columns)
    ]
    return ColumnBatch(b.schema, cols, out_live,
                       jnp.sum(out_live).astype(jnp.int32))


def exchange_slots(plan: PhysicalPlan, sides, mesh) -> List[dict]:
    """Slots a destination for each side of one exchange, from the counts
    the exchange itself will send: ``sides`` is ``[(name, stacked batch,
    hash exprs, evaluator)]``. One small SPMD program hashes every side
    and takes, over the whole mesh, the largest count any device has for
    any destination and the live rows; ONE host read (n_dev-replicated
    int32s, not data) then sizes the send buffers at the bucket of that
    count, so a device receives about the rows bound for it where a
    whole input capacity a source was ``n_dev`` times that. The same
    data gives the same bucket, so a warm plan meets the programs it
    compiled. Returns a side's ``slots`` with what ``note_exchange``
    says of it at every launch: the live ``rows``, and the ``slots``
    and ``bytes`` over the whole mesh."""
    from functools import partial

    from jax import shard_map

    from ..compile import MESH_NS_CAP, governed

    n_dev = mesh.devices.size
    axis = mesh.axis_names[0]
    stacked = tuple(st for _, st, _, _ in sides)

    def build():
        hashed = [(hx, ev) for _, _, hx, ev in sides]

        @partial(shard_map, mesh=mesh, in_specs=(P(axis),),
                 out_specs=P(axis), check_vma=False)
        def run(stacked_sides):
            out = []
            for st, (hx, ev) in zip(stacked_sides, hashed):
                b = jax.tree.map(lambda x: x[0], st)
                counts = mesh_shuffle.destination_counts(
                    b.selection, _partition_ids(b, hx, n_dev, ev), n_dev)
                out += [jax.lax.pmax(jnp.max(counts), axis),
                        jax.lax.psum(jnp.sum(counts), axis)]
            return jnp.stack(out)[None]

        return run

    key = ("mesh.exchange_counts", plan.compile_signature(),
           tuple(name for name, _, _, _ in sides), mesh,
           tuple(int(st.selection.shape[1]) for st in stacked),
           jax.tree.structure(stacked))
    counted = governed(key, build, cap=MESH_NS_CAP,
                       metrics=plan.metrics())(stacked)
    from ..observability.tracing import trace_span

    with trace_span("device.block", site="mesh.exchange_counts",
                    n=len(sides)):
        # replicated by the pmax/psum: any local shard holds them all
        got = np.asarray(counted.addressable_shards[0].data).reshape(-1)
    planned = []
    for i, (name, st, _, _) in enumerate(sides):
        largest, rows = int(got[2 * i]), int(got[2 * i + 1])
        cap = bucket_capacity(max(largest, 1))
        # the values of every column, and one int32 word of validity bits
        width = 4 + sum(c.values.dtype.itemsize for c in st.columns)
        planned.append({"slots": cap, "event": dict(
            side=name, rows=rows, slots=n_dev * n_dev * cap,
            bytes=rows * width, n_dev=n_dev)})
    return planned


def note_exchange(planned: List[dict]) -> None:
    """One ``mesh.exchange`` event a side, at the launch that sends it."""
    from .mesh_input import note

    for side in planned:
        note("exchanges", "mesh.exchange", **side["event"])


def _host_visible(stacked, mesh):
    """Make a stacked output sliceable on THIS process: under a
    multi-process (cross-host) mesh, some shards live on other
    processes, so the (small) final output is all_gather-replicated
    first; single-process meshes pass through untouched."""
    from ..parallel.multihost import is_multiprocess, replicate_stacked

    if not is_multiprocess():
        return stacked
    return replicate_stacked(stacked, mesh)


class _SchemaOnly(PhysicalPlan):
    """Placeholder child that only carries a schema (the mesh runner
    feeds batches directly, there is nothing to execute)."""

    def __init__(self, schema: Schema):
        self._schema = schema

    def output_schema(self) -> Schema:
        return self._schema

    def with_new_children(self, children):
        return self


class MeshAggExec(PhysicalPlan):
    """One task that replaces a whole shuffle stage pair.

    ``producer`` is the shuffle stage's child (scan -> ... -> partial
    aggregate, P partitions, executed on host); its output rows are laid
    out over an ``n_devices`` mesh and exchanged over ICI.
    Output: a single partition containing every device's final groups.
    """

    def __init__(self, producer: PhysicalPlan, group_exprs: List[ex.Expr],
                 agg_exprs: List[ex.Expr], hash_exprs: List[ex.Expr],
                 n_devices: int,
                 group_capacity: int = DEFAULT_GROUP_CAPACITY):
        self.producer = producer
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        self.hash_exprs = list(hash_exprs)
        self.n_devices = n_devices
        self.group_capacity = group_capacity
        self._partial_schema = producer.output_schema()
        self._final = HashAggregateExec(
            "final", self.group_exprs, self.agg_exprs,
            _SchemaOnly(self._partial_schema), group_capacity,
        )
        self._ev = Evaluator(self._partial_schema)

    # -- plan plumbing -------------------------------------------------------

    def output_schema(self) -> Schema:
        return self._final.output_schema()

    def output_partitioning(self) -> Partitioning:
        return Partitioning("unknown", 1)

    def children(self):
        return [self.producer]

    def with_new_children(self, children):
        return MeshAggExec(children[0], self.group_exprs, self.agg_exprs,
                           self.hash_exprs, self.n_devices,
                           self.group_capacity)

    def display(self) -> str:
        g = ", ".join(e.name() for e in self.group_exprs)
        return (f"MeshAggExec: {self.n_devices}-device ICI all_to_all "
                f"shuffle + final agg gby=[{g}]")

    def _signature_parts(self) -> tuple:
        from ..compile import fingerprint

        return (fingerprint(self.group_exprs), fingerprint(self.agg_exprs),
                fingerprint(self.hash_exprs), self.n_devices,
                self._partial_schema)

    def _detach(self) -> None:
        from .base import SchemaLeaf

        # _final's child is already schema-only; only the producer
        # subtree (scans and their caches) must be severed
        self.producer = SchemaLeaf(self._partial_schema)

    # -- execution -----------------------------------------------------------

    def _spmd(self, stacked, mesh, cap: int, slots: int):
        """(stacked batch pytree) -> (stacked out batch, num_groups[n])."""
        from functools import partial

        from jax import shard_map

        from ..compile import governed
        from .mesh_input import _MESH_NS_CAP

        n_dev = self.n_devices

        def build():
            tw = self.trace_twin()
            final_fn = self._final._get_grouped_fn(cap, n_dev * slots)

            @partial(shard_map, mesh=mesh, in_specs=(P("data"),),
                     out_specs=(P("data"), P("data")), check_vma=False)
            def run(stacked_b):
                b = jax.tree.map(lambda x: x[0], stacked_b)
                b2 = _shuffle_side(b, tw.hash_exprs, tw._ev, n_dev,
                                   slots)
                out_batch, num_groups = final_fn(b2)
                return (
                    jax.tree.map(lambda x: x[None], out_batch),
                    num_groups[None],
                )

            return run

        key = ("mesh.agg_spmd", self.compile_signature(), mesh, cap,
               slots, jax.tree.structure(stacked))
        return governed(key, build, cap=_MESH_NS_CAP,
                        metrics=self.metrics())(stacked)

    def execute_stacked(self, mesh) -> ColumnBatch:
        """Device-resident execution: stacked [n_dev, cap] output sharded
        over the mesh — consumed directly by a chained fused stage (HBM
        partition cache) or sliced per device by ``execute``."""
        from ..parallel.multihost import host_max
        from .mesh_input import stacked_input

        stacked, _ = stacked_input(self.producer, self._partial_schema,
                                   mesh)
        planned = exchange_slots(
            self, [("agg", stacked, self.hash_exprs, self._ev)], mesh)
        slots = planned[0]["slots"]
        cap = self.group_capacity
        while True:
            note_exchange(planned)
            out_stacked, num_groups = self._spmd(stacked, mesh, cap, slots)
            ng = host_max(num_groups)  # multihost-safe replicated max
            if ng <= cap:
                return out_stacked
            cap = round_capacity(ng)  # overflow: recompile with exact cap

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        if partition != 0:
            raise ExecutionError("MeshAggExec has a single output partition")
        mesh = make_mesh(self.n_devices)
        out_stacked = _host_visible(self.execute_stacked(mesh), mesh)
        for q in range(self.n_devices):
            yield jax.tree.map(lambda x, _q=q: jnp.asarray(x)[_q],
                               out_stacked)


def _partition_ids(batch: ColumnBatch, hash_exprs, n_dev: int,
                   ev: Evaluator):
    from .operators import compute_partition_ids

    return compute_partition_ids(batch, hash_exprs, n_dev, 0, ev)

class MeshJoinExec(PhysicalPlan):
    """Mesh-fused co-partitioned join: BOTH join inputs are exchanged
    over ICI ``lax.all_to_all`` (hashed on the join keys) and joined per
    device in the same SPMD program — BASELINE config 4's shape
    ("q5 shuffle -> ICI all_to_all") with zero shuffle files.

    Built by the scheduler's fusion pass from a partitioned JoinExec
    stage and its two hash-shuffle producer stages. Supports every host
    join type (inner/left/semi/anti/full): co-partitioning makes
    unmatched-row detection local to each device, so outer rows are
    appended after the matched expansion in the same static output
    buffer (host semantics: physical/join.py:292-357). Key
    representation is raw values for one key column, the exact rank
    codec otherwise — decided statically, no host-side range checks.
    Output: a single partition containing every device's joined rows
    (adaptive output capacity with whole-SPMD retry on overflow, like
    MeshAggExec).
    """

    def __init__(self, build_producer: PhysicalPlan,
                 probe_producer: PhysicalPlan, on, how: str,
                 n_devices: int, null_aware: bool = False,
                 out_columns=None):
        if how not in ("inner", "left", "semi", "anti", "full"):
            raise ExecutionError(f"MeshJoinExec join type {how}")
        self.null_aware = null_aware
        self.build_producer = build_producer
        self.probe_producer = probe_producer
        self.on = list(on)
        self.how = how
        self.n_devices = n_devices
        from .join import JoinExec

        # schema/key helpers only; never executed
        self._join = JoinExec(
            _SchemaOnly(build_producer.output_schema()),
            _SchemaOnly(probe_producer.output_schema()),
            self.on, how, out_columns=out_columns,
        )
        self._build_ev = Evaluator(build_producer.output_schema())
        self._probe_ev = Evaluator(probe_producer.output_schema())

    # -- plan plumbing -------------------------------------------------------

    def output_schema(self) -> Schema:
        return self._join.output_schema()

    @property
    def out_columns(self):
        """The columns the join it replaced emitted (``JoinExec``)."""
        return self._join.out_columns

    def output_partitioning(self) -> Partitioning:
        return Partitioning("unknown", 1)

    def children(self):
        return [self.build_producer, self.probe_producer]

    def with_new_children(self, children):
        return MeshJoinExec(children[0], children[1], self.on, self.how,
                            self.n_devices, self.null_aware,
                            self.out_columns)

    def display(self) -> str:
        on = ", ".join(f"{l}={r}" for l, r in self.on)
        return (f"MeshJoinExec: {self.n_devices}-device ICI all_to_all "
                f"join how={self.how} on=[{on}]{self._join._out_label()}")

    def _signature_parts(self) -> tuple:
        return (self.how, tuple(self.on), self.null_aware, self.n_devices,
                self.build_producer.output_schema(),
                self.probe_producer.output_schema(), self.out_columns)

    def _detach(self) -> None:
        from .base import SchemaLeaf

        self.build_producer = SchemaLeaf(self.build_producer.output_schema())
        self.probe_producer = SchemaLeaf(self.probe_producer.output_schema())
        # _join's children are already schema-only, but execute_stacked
        # fills its _remap_cache with per-query dictionaries — take its
        # own (cache-cleared) twin so the governed entry pins none
        self._join = self._join.trace_twin()

    # -- execution -----------------------------------------------------------

    def _hash_exprs(self):
        """(build, probe) hash expressions: the join keys of each side."""
        return ([ex.ColumnRef(b) for b, _ in self.on],
                [ex.ColumnRef(p) for _, p in self.on])

    def _spmd(self, stacked_b, stacked_p, mesh, remaps, out_cap: int,
              b_slots: int, p_slots: int):
        from functools import partial as fpartial

        from ..kernels import join as join_k
        from jax import shard_map

        def build():
            # whole closure construction deferred: on a governed cache hit
            # none of this work (twin, hash exprs, shard_map wrapping) runs
            n_dev = self.n_devices
            bcols = [b for b, _ in self.on]
            pcols = [p for _, p in self.on]
            bhash, phash = self._hash_exprs()
            out_schema = self.output_schema()
            probe_schema = self.probe_producer.output_schema()
            tw = self.trace_twin()

            @fpartial(shard_map, mesh=mesh,
                      in_specs=(P("data"), P("data"), P()),
                      out_specs=(P("data"), P("data")), check_vma=False)
            def run(sb, sp, remaps):
              b = jax.tree.map(lambda x: x[0], sb)
              p = jax.tree.map(lambda x: x[0], sp)
              b2 = _shuffle_side(b, bhash, tw._build_ev, n_dev, b_slots)
              p2 = _shuffle_side(p, phash, tw._probe_ev, n_dev, p_slots)
              # keys: raw for a single column, exact rank codec otherwise
              if len(tw.on) == 1:
                  bk = b2.column(bcols[0]).values.astype(jnp.int64)
                  blive = b2.selection
                  v = b2.column(bcols[0]).validity
                  if v is not None:
                      blive = jnp.logical_and(blive, v)
                  pk, pvalid = tw._join._probe_col_values(
                      p2, pcols[0], remaps[0])
                  plive = p2.selection
                  if pvalid is not None:
                      plive = jnp.logical_and(plive, pvalid)
              else:
                  bk, blive, (tables, nlive) = tw._join._codec_build(
                      b2, bcols)
                  pk, plive = tw._join._probe_keys(p2, "codec",
                                                     (tables, nlive), remaps)
              table = join_k.build_lookup(bk, blive)

              if tw.how in ("semi", "anti"):
                  # membership only: probe-aligned output, no expansion
                  matched = join_k.probe_semi(table, pk, plive)
                  if tw.how == "semi":
                      sel = jnp.logical_and(p2.selection, matched)
                  else:
                      sel = jnp.logical_and(p2.selection,
                                            jnp.logical_not(matched))
                      if tw.null_aware:
                          # SQL NOT IN: a null key ANYWHERE in the build
                          # side (any device) makes the predicate never
                          # true; null-key probe rows are dropped too
                          bnull = jnp.logical_and(b2.selection,
                                                  jnp.logical_not(blive))
                          bnull_any = jax.lax.pmax(
                              jnp.max(bnull.astype(jnp.int32)), "data") > 0
                          for _, pcol in tw.on:
                              vv = p2.column(pcol).validity
                              if vv is not None:
                                  sel = jnp.logical_and(sel, vv)
                          sel = jnp.logical_and(sel,
                                                jnp.logical_not(bnull_any))
                  out = p2.with_selection(sel)
                  need = jnp.zeros((), jnp.int32)
                  return jax.tree.map(lambda x: x[None], out), need[None]

              prows, brows, olive, total = join_k.probe_expand(
                  table, pk, plive, out_cap)
              need = total
              C = out_cap
              # outer rows: co-partitioning makes unmatched detection
              # local; append them after the matched expansion in the same
              # static buffer (overflow rides the same retry as matches)
              sidx_p = sidx_b = None
              n_up = jnp.zeros((), jnp.int32)
              if tw.how in ("left", "full"):
                  counts = join_k.probe_counts(table, pk)
                  un_p = jnp.logical_and(
                      p2.selection,
                      jnp.logical_or(jnp.logical_not(plive), counts == 0))
                  rank_p = jnp.cumsum(un_p.astype(jnp.int32)) - un_p
                  n_up = jnp.sum(un_p.astype(jnp.int32))
                  sidx_p = jnp.where(un_p, total + rank_p, C)  # C drops
                  need = need + n_up
              if tw.how == "full":
                  pt = join_k.build_lookup(pk, plive)
                  _, bmat = join_k.probe_unique(pt, bk, blive)
                  un_b = jnp.logical_and(
                      b2.selection,
                      jnp.logical_not(jnp.logical_and(blive, bmat)))
                  rank_b = jnp.cumsum(un_b.astype(jnp.int32)) - un_b
                  sidx_b = jnp.where(un_b, total + n_up + rank_b, C)
                  need = need + jnp.sum(un_b.astype(jnp.int32))

              live = olive
              if sidx_p is not None:
                  live = live.at[sidx_p].set(True, mode="drop")
              if sidx_b is not None:
                  live = live.at[sidx_b].set(True, mode="drop")

              cols = []
              for f in out_schema.fields:
                  from_probe = probe_schema.has_field(f.name)
                  src = p2 if from_probe else b2
                  rows = prows if from_probe else brows
                  c = src.column(f.name)
                  vals = jnp.take(c.values, rows)
                  validity = (jnp.take(c.validity, rows)
                              if c.validity is not None else None)
                  src_valid = (c.validity if c.validity is not None
                               else True)
                  if from_probe:
                      if sidx_p is not None:
                          vals = vals.at[sidx_p].set(c.values, mode="drop")
                          if validity is not None:
                              validity = validity.at[sidx_p].set(
                                  src_valid, mode="drop")
                      if sidx_b is not None:  # probe cols null on
                          if validity is None:  # build-only rows
                              validity = jnp.ones((C,), jnp.bool_)
                          validity = validity.at[sidx_b].set(
                              False, mode="drop")
                  else:
                      if sidx_p is not None:  # build cols null on
                          if validity is None:  # probe-only rows
                              validity = jnp.ones((C,), jnp.bool_)
                          validity = validity.at[sidx_p].set(
                              False, mode="drop")
                      if sidx_b is not None:
                          vals = vals.at[sidx_b].set(c.values, mode="drop")
                          validity = validity.at[sidx_b].set(
                              src_valid, mode="drop")
                  cols.append(Column(vals, f.dtype, validity, c.dictionary))
              out = ColumnBatch(out_schema, cols, live,
                                jnp.sum(live).astype(jnp.int32))
              return jax.tree.map(lambda x: x[None], out), need[None]

            return run

        from ..compile import MESH_NS_CAP, governed

        key = ("mesh.join_spmd", self.compile_signature(), mesh, out_cap,
               b_slots, p_slots,
               jax.tree.structure((stacked_b, stacked_p, remaps)))
        return governed(key, build, cap=MESH_NS_CAP,
                        metrics=self.metrics())(stacked_b, stacked_p,
                                                remaps)

    def execute_stacked(self, mesh) -> ColumnBatch:
        """Device-resident execution: both inputs laid out over the mesh
        (or taken straight from chained fused producers), joined in one
        SPMD program; stacked [n_dev, out_cap] output stays sharded."""
        from .mesh_input import stacked_input

        sb, _ = stacked_input(
            self.build_producer, self.build_producer.output_schema(), mesh)
        sp, _ = stacked_input(
            self.probe_producer, self.probe_producer.output_schema(), mesh)
        remaps = self._join._remaps_for(sb, sp)
        from ..parallel.multihost import host_max

        bhash, phash = self._hash_exprs()
        planned = exchange_slots(
            self, [("build", sb, bhash, self._build_ev),
                   ("probe", sp, phash, self._probe_ev)], mesh)
        b_slots, p_slots = (side["slots"] for side in planned)
        out_cap = self.n_devices * p_slots  # post-shuffle probe rows/device
        if self.how == "full":  # + room for unmatched build rows
            out_cap = bucket_capacity(out_cap + self.n_devices * b_slots)
        while True:
            note_exchange(planned)
            out_stacked, totals = self._spmd(sb, sp, mesh, remaps, out_cap,
                                             b_slots, p_slots)
            t = host_max(totals)  # multihost-safe replicated max
            if t <= out_cap:
                return out_stacked
            out_cap = bucket_capacity(t)  # duplicate-heavy keys: retry

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        if partition != 0:
            raise ExecutionError("MeshJoinExec has a single output partition")
        from .base import maybe_compact

        mesh = make_mesh(self.n_devices)
        out_stacked = _host_visible(self.execute_stacked(mesh), mesh)
        for q in range(self.n_devices):
            # selective joins (semi/anti especially) leave mostly-dead
            # slices; shrink them like the host join does before handing
            # batches to downstream host operators
            yield maybe_compact(jax.tree.map(
                lambda x, _q=q: jnp.asarray(x)[_q], out_stacked))
