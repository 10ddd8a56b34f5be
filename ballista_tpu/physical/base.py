"""Physical plan base classes + batch utilities.

Execution model: ``execute(partition)`` yields ColumnBatches (host-driven
volcano at batch granularity), but *pipeline* operators (filter/projection/
partial-agg input chains) are traced together and jitted, so a whole chain
runs as ONE fused XLA program per batch — the TPU-native answer to the
reference's per-operator Rust volcano streams (reference:
rust/core/src/execution_plans/query_stage.rs:29-85 executes DataFusion
streams operator-by-operator).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnBatch
from ..compile import bucket_capacity, governed
from ..datatypes import Schema
from ..errors import ExecutionError
from ..kernels.search import (  # noqa: F401 - compact_perm's shapes
    BLOCK as _BLOCK, COUNT_ROW as _COUNT_ROW, QUERY_CHUNK as _QUERY_CHUNK,
    TOP as _TOP, first_live)
from ..observability.metrics import (MetricsSet, instrument_execute,
                                     metrics_enabled)
from ..observability.tracing import trace_event


@dataclass(frozen=True)
class Partitioning:
    """Output partitioning descriptor."""

    kind: str  # "unknown" | "round_robin" | "hash"
    num_partitions: int
    hash_columns: tuple = ()


# Split-call donation convention (cache/donation.py): the batch treedef
# is static (arg 0), the column/validity/selection leaves are the
# donated payload (arg 1), num_rows rides as a plain argument (arg 2) —
# never donated, see PhysicalPlan.governed_call.
DONATING_JIT_KWARGS = {"static_argnums": (0,), "donate_argnums": (1,)}


def _donating_build(build):
    """Wrap a ``build()`` producing ``run(batch, *extra)`` into one
    producing the split-call form ``run(treedef, payload, num_rows,
    *extra)`` that reconstructs the batch inside the trace. num_rows is
    the LAST flattened leaf (columnar._flatten_batch), so unflatten
    appends it to the payload."""

    def build_donating():
        run = build()

        def run_split(treedef, payload, num_rows, *extra):
            batch = jax.tree_util.tree_unflatten(
                treedef, list(payload) + [num_rows])
            return run(batch, *extra)

        return run_split

    return build_donating


class PhysicalPlan:
    """Base physical operator.

    Every subclass that overrides ``execute`` is transparently
    instrumented (``__init_subclass__`` below): each call records
    ``output_rows``/``output_batches``/``elapsed_compute`` on the
    operator's :class:`MetricsSet` with zero per-operator boilerplate.
    Operators add their own domain counters (compaction, shuffle bytes,
    expand re-runs) via ``self.metrics()``.
    """

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        exec_fn = cls.__dict__.get("execute")
        if exec_fn is not None:
            cls.execute = instrument_execute(exec_fn)

    def metrics(self) -> MetricsSet:
        """The operator's MetricsSet (lazily created). Plain instance
        state, same benign-race policy as the adaptive counters below:
        concurrent partition execution can interleave updates and lose
        an increment, which skews a displayed number, never a result."""
        m = getattr(self, "_metrics", None)
        if m is None:
            m = self._metrics = MetricsSet()
        return m

    # -- compile governor ---------------------------------------------------

    def compile_signature(self) -> tuple:
        """Value-signature of everything this operator's traced closures
        read from instance state. Governed jit keys include it, so two
        instances with equal signatures (e.g. the same operator before
        and after an adaptive re-plan) share one compiled entry. The
        default covers operators whose ``display()`` renders their full
        configuration; operators with trace-relevant state beyond that
        override ``_signature_parts``."""
        sig = getattr(self, "_compile_sig", None)
        if sig is None:
            sig = self._compile_sig = (
                (type(self).__name__,) + self._signature_parts()
            )
        return sig

    def _signature_parts(self) -> tuple:
        return (self.display(), self.output_schema())

    def governed_jit(self, subkey: tuple, build, **kw):
        """Process-wide compiled function for this operator under
        ``subkey`` (namespace first); compiles it triggers are
        attributed to this operator's metrics. Replaces the per-instance
        ``self._jit_*`` dicts, which adaptive re-planning (new operator
        instances) used to throw away."""
        key = (subkey[0], self.compile_signature()) + tuple(subkey[1:])
        metrics = self.metrics() if metrics_enabled() else None
        return governed(key, build, metrics=metrics, **kw)

    def governed_call(self, subkey: tuple, build, batch, *extra):
        """Run the governed program under ``subkey`` on ``batch``,
        donating the batch's device buffers when it is transient
        (single-consumer intermediate, cache/donation.py) and donation
        is enabled. A TUPLE of batches (an aggregate's partition handed
        over as it arrived) is never transient and donates nothing.
        The donating variant is a SEPARATE governed entry
        (``<namespace>.don``) because its call convention splits the
        batch: the treedef rides as a static argument,
        column/validity/selection leaves are the donated payload, and
        ``num_rows`` stays an ordinary argument —
        MetricsSet.record_output_batch holds that scalar in
        ``_pending_rows`` long after the batch body is consumed, so
        donating it would hand ``_resolve_rows`` deleted buffers."""
        from ..cache.donation import (consume_transient, donation_enabled,
                                      record_donation)

        if donation_enabled() and consume_transient(batch):
            fn = self.governed_jit(
                (subkey[0] + ".don",) + tuple(subkey[1:]),
                _donating_build(build),
                jit_kwargs=dict(DONATING_JIT_KWARGS))
            leaves, treedef = jax.tree_util.tree_flatten(batch)
            payload, num_rows = tuple(leaves[:-1]), leaves[-1]
            record_donation(sum(int(getattr(x, "nbytes", 0))
                                for x in payload))
            return fn(treedef, payload, num_rows, *extra)
        return self.governed_jit(subkey, build)(batch, *extra)

    # -- adaptive compaction ------------------------------------------------
    #
    # The ONE rule for operators whose input transform can kill rows
    # (a pipeline chain with a FilterExec; a JoinExec whose fused probe
    # chain holds one): read the live count of what the transform left,
    # compact when under a quarter survives (maybe_compact), and learn.
    # A filter's selectivity is stationary within a query, so after 2
    # consecutive batches that decline, stop paying the per-batch
    # live-count sync for the operator's lifetime (it would otherwise
    # serialize host scan parsing against device compute batch-by-batch
    # for zero benefit on unselective filters). The learned capacity
    # floor keeps later batches from compacting to ever-different
    # power-of-two rungs, bounding downstream per-capacity jit compiles
    # to ~one extra.
    #
    # BENIGN RACE: _compact_misses/_compact_floor are unsynchronized
    # instance state; executor worker threads running partitions of one
    # operator concurrently can interleave updates. Outcomes stay
    # correct — these only steer heuristics — but learned values can
    # thrash; the same policy covers the MetricsSet counters.

    def still_compacting(self) -> bool:
        """False once two batches in a row declined to compact: from
        then on the operator reads no live count."""
        return getattr(self, "_compact_misses", 0) < 2

    def compact_learning(self, batch: ColumnBatch) -> ColumnBatch:
        """``maybe_compact`` at the learned floor (one blocking count
        read), recording whether the batch shrank. Returns ``batch``
        itself when it declined."""
        floor = getattr(self, "_compact_floor", 8)
        res = maybe_compact(batch, floor=floor)
        if res is batch:
            self._compact_misses = getattr(self, "_compact_misses", 0) + 1
        else:
            self._compact_misses = 0
            self._compact_floor = max(floor, res.capacity)
            self.metrics().add_counter("compact_count")
        return res

    def trace_twin(self) -> "PhysicalPlan":
        """Config-only shallow clone for governed closures to capture.

        Governed entries outlive operator instances, so a closure over
        ``self`` would pin the whole plan subtree — cached scan batches,
        repartition materializations, join build-side device buffers —
        for as long as the compiled entry lives. The twin carries
        everything traced closures actually read (mode/exprs/schemas/
        evaluators) while ``_detach`` severs children and data caches.
        Closures passed to ``governed_jit`` must reference the twin,
        never ``self``."""
        tw = getattr(self, "_trace_twin", None)
        if tw is None:
            import copy

            tw = copy.copy(self)
            self._trace_twin = tw
            tw._trace_twin = tw  # twin of the twin is itself
            tw._metrics = None
            tw._detach()
        return tw

    def _detach(self) -> None:
        """Sever plan-subtree and materialized-state references on a
        trace twin (runs on the COPY). Default: children become
        schema-only leaves. Operators whose traced closures read other
        heavy members override and extend."""
        if getattr(self, "child", None) is not None:
            self.child = SchemaLeaf(self.child.output_schema())
        if getattr(self, "_fused_fn", None) is not None:
            self._fused_fn = None  # no entry->twin->entry cycles
        if getattr(self, "_fused_don_fn", None) is not None:
            self._fused_don_fn = None

    def output_schema(self) -> Schema:
        raise NotImplementedError

    def output_partitioning(self) -> Partitioning:
        cs = self.children()
        if cs:
            return cs[0].output_partitioning()
        return Partitioning("unknown", 1)

    def children(self) -> List["PhysicalPlan"]:
        return []

    def with_new_children(self, children: List["PhysicalPlan"]) -> "PhysicalPlan":
        raise NotImplementedError(type(self).__name__)

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        raise NotImplementedError(type(self).__name__)

    def estimated_rows(self) -> Optional[int]:
        """Crude output-cardinality estimate for planning decisions (e.g.
        picking a partitioned join when the build side is large). Filters
        and joins deliberately over-estimate (pass-through / sum); None =
        unknown."""
        ests = [c.estimated_rows() for c in self.children()]
        # any unknown child makes the total unknown: silently dropping it
        # would UNDER-estimate, and callers rely on over-estimation
        if not ests or any(e is None for e in ests):
            return None
        return sum(ests)

    def display(self) -> str:
        return type(self).__name__

    def pretty(self, indent: int = 0) -> str:
        out = "  " * indent + self.display() + "\n"
        for c in self.children():
            out += c.pretty(indent + 1)
        return out

    def pretty_metrics(self, indent: int = 0) -> str:
        """Plan text annotated with live metrics (EXPLAIN ANALYZE).
        Operators fused into a pipeline chain show no numbers of their
        own — the chain's totals sit on its outermost operator."""
        ann = self.metrics().summary()
        out = ("  " * indent + self.display()
               + (f", metrics=[{ann}]" if ann else "") + "\n")
        for c in self.children():
            out += c.pretty_metrics(indent + 1)
        return out


def side_name(plan: PhysicalPlan) -> str:
    """What a subtree is made from, for the spans of the operators that
    keep its output (a join's build, a repartition's sources): the tables
    of the scans under it in name order, ``customer+orders`` for the
    output of their join; the operator's own name where it scans none."""
    tables, stack = set(), [plan]
    while stack:
        node = stack.pop()
        name = getattr(node, "table_name", None)
        if name:
            tables.add(name)
        stack.extend(node.children())
    return "+".join(sorted(tables)) or type(plan).__name__


class SchemaLeaf(PhysicalPlan):
    """Schema-only placeholder standing in for a severed child on a
    trace twin (mirrors mesh_agg's _SchemaOnly, but importable from
    base without cycles). Never executed."""

    def __init__(self, schema: Schema):
        self._schema = schema

    def output_schema(self) -> Schema:
        return self._schema

    def with_new_children(self, children):
        return self


class PipelineOp(PhysicalPlan):
    """Operator whose work is a pure batch->batch device transform.

    Chains of PipelineOps are fused into one jitted function; the chain's
    non-pipeline root feeds batches through it.
    """

    child: PhysicalPlan
    # True for transforms that can kill rows (FilterExec): the fused
    # chain's output is then adaptively compacted, so a selective filter
    # hands every downstream operator a capacity sized to the survivors
    # instead of the scan's (q15's 3-month window keeps 7.5% of lineitem
    # but aggregation paid full-capacity passes). Same policy/guards as
    # post-join compaction (maybe_compact: >=4x shrink, sync-cost-aware).
    compactable = False

    def device_transform(self, batch: ColumnBatch) -> ColumnBatch:
        raise NotImplementedError(type(self).__name__)

    def children(self) -> List[PhysicalPlan]:
        return [self.child]

    # fused execution ------------------------------------------------------

    def _pipeline_chain(self):
        """(transforms outer-to-inner reversed into apply order, source op)."""
        chain: List[PipelineOp] = []
        node: PhysicalPlan = self
        while isinstance(node, PipelineOp):
            chain.append(node)
            node = node.child
        chain.reverse()  # innermost transform first
        return chain, node

    def _fused_governed(self):
        """Governed fused transform for this operator's pipeline chain.
        Keyed on the chain's operator signatures, so a re-planned stage
        (fresh instances, same logical chain) reuses the compiled
        programs; compile time lands on this operator's metrics."""
        fused = getattr(self, "_fused_fn", None)
        if fused is None:
            chain, _ = self._pipeline_chain()

            def build():
                # twins: device_transform reads exprs/evaluators, never
                # .child — capturing the live ops would pin the source
                # (and its cached batches) in the process-wide cache
                twins = [op.trace_twin() for op in chain]

                def apply_all(batch):
                    for op in twins:
                        batch = op.device_transform(batch)
                    return batch

                return apply_all

            key = ("pipeline.fused",
                   tuple(op.compile_signature() for op in chain))
            metrics = self.metrics() if metrics_enabled() else None
            fused = self._fused_fn = governed(key, build, metrics=metrics)
        return fused

    def _fused_governed_donating(self):
        """Donating twin of :meth:`_fused_governed` (split-call
        convention, see ``governed_call``): used per-batch when the
        incoming batch is transient. Shares the chain-signature key
        shape under the ``pipeline.fused.don`` namespace."""
        fused = getattr(self, "_fused_don_fn", None)
        if fused is None:
            chain, _ = self._pipeline_chain()

            def build():
                twins = [op.trace_twin() for op in chain]

                def apply_all(batch):
                    for op in twins:
                        batch = op.device_transform(batch)
                    return batch

                return apply_all

            key = ("pipeline.fused.don",
                   tuple(op.compile_signature() for op in chain))
            metrics = self.metrics() if metrics_enabled() else None
            fused = self._fused_don_fn = governed(
                key, _donating_build(build), metrics=metrics,
                jit_kwargs=dict(DONATING_JIT_KWARGS))
        return fused

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        from ..cache.donation import (consume_transient, donation_enabled,
                                      mark_transient, record_donation)

        chain, source = self._pipeline_chain()
        fused = self._fused_governed()
        # a chain that can kill rows compacts its output under the one
        # adaptive rule (PhysicalPlan.compact_learning)
        compact = any(op.compactable for op in chain)
        for batch in source.execute(partition):
            # the governor records the compile-vs-execute split: a call
            # that triggers an XLA compile lands its duration on this
            # operator's elapsed_compile / compile_count metrics
            if donation_enabled() and consume_transient(batch):
                # single-consumer scan/concat output: hand XLA the
                # buffers so the fused program writes in place instead
                # of allocating a second copy of the batch
                leaves, treedef = jax.tree_util.tree_flatten(batch)
                payload, num_rows = tuple(leaves[:-1]), leaves[-1]
                record_donation(sum(int(getattr(x, "nbytes", 0))
                                    for x in payload))
                out = self._fused_governed_donating()(
                    treedef, payload, num_rows)
            else:
                out = fused(batch)
            if compact and self.still_compacting():
                out = self.compact_learning(out)
            # fresh XLA output (or fresh compaction), exactly one
            # downstream consumer: donation-eligible
            mark_transient(out)
            yield out


# ---------------------------------------------------------------------------
# Batch utilities shared by operators
# ---------------------------------------------------------------------------


def shares_dictionaries(batches: Sequence[ColumnBatch]) -> bool:
    """True when every column carries ONE dictionary instance (or none)
    across ``batches``: the pieces of one scan, from the table cache or
    from a file. Shuffle partitions from independent producers do not."""
    first = batches[0].columns
    return all(c.dictionary is f.dictionary
               for b in batches[1:] for c, f in zip(b.columns, first))


def _columns_of(batches: Sequence[ColumnBatch]):
    """Per column of ``batches``: its pieces, whether the gathered column
    has a validity (any piece has one), and its dictionary. The one place
    that decides the gathered batch's pytree structure."""
    for i in range(len(batches[0].columns)):
        pieces = [b.columns[i] for b in batches]
        yield (pieces, any(c.validity is not None for c in pieces),
               next((c.dictionary for c in pieces
                     if c.dictionary is not None), None))


def gather_batches(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """One batch of the SUMMED capacity from pieces that share their
    dictionaries (``shares_dictionaries``): values, validity (all-true
    where a piece has none and another has one) and selection laid end
    to end in order, the live counts added. Pure jnp, so it runs eagerly
    for ``concat_batches`` and INSIDE a governed program's trace for an
    aggregate handed a partition's batches as they are
    (``HashAggregateExec._partition_input``), where the copy is XLA's to
    place and nothing is launched between the scan and the program."""
    cols = [
        Column(jnp.concatenate([c.values for c in pieces]), pieces[0].dtype,
               jnp.concatenate([c.valid_mask() for c in pieces])
               if has_validity else None, dict_)
        for pieces, has_validity, dict_ in _columns_of(batches)]
    selection = jnp.concatenate([b.selection for b in batches])
    num_rows = sum([b.num_rows for b in batches])
    return ColumnBatch(batches[0].schema, cols, selection, num_rows)


def gathered_shape(batches: Sequence[ColumnBatch]) -> ColumnBatch:
    """What ``gather_batches`` would return, as shapes only (dictionaries
    and validity presence ride the batch as they do the real one): for
    the host-side choice of an aggregation path, which reads capacity,
    dictionaries and which columns have a validity. No trace, no
    launch."""
    cap = sum(b.capacity for b in batches)

    def shaped(x):
        return jax.ShapeDtypeStruct((cap,) + tuple(x.shape[1:]), x.dtype)

    live = shaped(batches[0].selection)
    cols = [Column(shaped(pieces[0].values), pieces[0].dtype,
                   live if has_validity else None, dict_)
            for pieces, has_validity, dict_ in _columns_of(batches)]
    return ColumnBatch(batches[0].schema, cols, live,
                       jax.ShapeDtypeStruct((), jnp.int32))


def concat_batches(schema: Schema, batches: List[ColumnBatch]) -> ColumnBatch:
    """Concatenate batches (device) into one larger-capacity batch.

    utf8 columns whose batches carry DIFFERENT dictionaries (e.g. shuffle
    partitions from independent producers) are unified: a sorted union
    dictionary is built host-side and each batch's codes are remapped.
    Host-level only — never call inside a jit trace (``gather_batches``
    is the part of it that may be).

    Output capacity is the exact SUM of the inputs, deliberately NOT
    padded up to a bucket-ladder rung: inputs are already ladder-sized,
    so concat capacities quantize to rung sums (e.g. k * 2^20 for a
    k-chunk scan) — a bounded shape family — while padding to the next
    rung would make the downstream sort/aggregate touch up to ~2x the
    rows (q1's 6-chunk concat would grow 6M -> 8.4M), blowing the warm-
    throughput budget for a marginal compile saving. RepartitionExec is
    the exception (it pads): its fragment concats produce genuinely
    irregular sums across partitions of one shuffle.
    """
    if not batches:
        raise ExecutionError("concat of zero batches")
    if len(batches) == 1:
        return batches[0]
    if not shares_dictionaries(batches):
        batches = _unify_dictionaries(schema, batches)
    out = gather_batches(batches)
    # fresh jnp.concatenate buffers with exactly one consumer (the
    # aggregation/sort program the concat feeds): donation-eligible.
    # The len == 1 pass-through above deliberately inherits the input's
    # own transiency instead — pinned cache batches stay pinned.
    from ..cache.donation import mark_transient

    mark_transient(out)
    return out


def _unify_dictionaries(schema: Schema, batches: List[ColumnBatch]
                        ) -> List[ColumnBatch]:
    """``batches`` with every utf8 column's codes remapped onto ONE
    dictionary a column, through the dictionary registry: shared-entry
    dictionaries resolve to a no-op or a cached int32 remap (a device
    gather); unregistered dictionaries fall back to the legacy sorted
    union inside the registry module. Host-level."""
    from ..observability import trace_span
    from .. import columnar_registry

    columns = [list(b.columns) for b in batches]
    for i, f in enumerate(schema.fields):
        dicts = [b.columns[i].dictionary for b in batches]
        dict_ = next((d for d in dicts if d is not None), None)
        if dict_ is None or all(d is None or d is dict_ for d in dicts):
            continue
        with trace_span("host.dictionary", site="concat.unify",
                        column=f.name, n_dicts=len(dicts)):
            target, remaps = columnar_registry.unify(dicts)
            for cols, remap in zip(columns, remaps):
                c = cols[i]
                values = c.values if remap is None else jnp.take(
                    jnp.asarray(remap), c.values.astype(jnp.int32),
                    mode="clip")
                cols[i] = Column(values, c.dtype, c.validity, target)
    return [ColumnBatch(b.schema, cols, b.selection, b.num_rows)
            for b, cols in zip(batches, columns)]


# Measured cost of a blocking scalar device->host read (seconds). Where
# one sync costs more than speculative compaction ever saves,
# maybe_compact only pays for a sync while syncs are known to be cheap.
_SYNC_COST: List[float] = []
_SYNC_COST_LIMIT = 0.005


def _record_sync_cost(batch: ColumnBatch) -> None:
    """Measure a PURE round-trip: re-fetch a scalar that is already on
    its way/ready, so pending compute doesn't inflate the figure."""
    import time as _time

    t0 = _time.perf_counter()
    int(batch.num_rows)
    _SYNC_COST.append(_time.perf_counter() - t0)


def maybe_compact(batch: ColumnBatch, shrink_factor: int = 4,
                  known_rows: Optional[int] = None,
                  floor: int = 8) -> ColumnBatch:
    """Shrink a sparse batch: when live rows fill under 1/shrink_factor
    of the capacity, gather them, in order, to the front of a smaller
    batch on the bucket ladder, so every downstream operator runs on
    the smaller shape — decisive after selective joins/filters in long
    pipelines. One governed program per target rung: compact_perm finds
    the survivors (one pass over the capacity, then a cost that follows
    the rung, not the capacity thrown away), take_batch gathers them,
    one gathered element per survivor and column. Each compaction counts
    one ``compact.search`` event in ``tracing.span_totals()``, with the
    capacity it came from.

    Callers: the adaptive rule (``PhysicalPlan.compact_learning``: a
    pipeline chain's output, and a join's probe batch BEFORE its probe
    when the fused probe chain holds a filter), and ``JoinExec`` after a
    probe-aligned (unique, semi, anti) probe of a batch that was not
    compacted before it. The expanding probe needs none: it sizes its
    output by the count it reads (``JoinExec._expand_run``).

    Pass ``known_rows`` when the live count is already on host — then
    this never blocks. Without it, the live-count sync is only paid
    while measured sync cost is low; on a remote accelerator the first
    call measures the round-trip and all later speculative syncs are
    skipped."""
    if known_rows is not None:
        n = known_rows
    else:
        if _SYNC_COST and _SYNC_COST[-1] > _SYNC_COST_LIMIT:
            return batch  # a sync costs more than compaction saves
        first = not _SYNC_COST
        n = int(batch.num_rows)
        if first:
            _record_sync_cost(batch)  # pure-RTT measurement
    cap = batch.capacity
    # compaction targets land on the bucket ladder: a selective filter's
    # survivors must not mint a fresh per-selectivity capacity downstream
    new_cap = max(bucket_capacity(n), floor, 8)
    if new_cap * shrink_factor > cap:
        return batch

    def build(_new=new_cap):
        def compact(b: ColumnBatch) -> ColumnBatch:
            perm = compact_perm(b.selection, _new)
            live = jnp.arange(_new, dtype=jnp.int32) < b.num_rows
            return take_batch(b, perm, live)

        return compact

    trace_event("compact.search", rows=n, capacity=cap, to=new_cap)
    return governed(("batch.compact", new_cap), build)(batch)


def pad_batch(batch: ColumnBatch, capacity: int) -> ColumnBatch:
    """Grow a batch's capacity with dead padding rows (device)."""
    if capacity <= batch.capacity:
        return batch
    extra = capacity - batch.capacity
    cols = []
    for col in batch.columns:
        vals = jnp.concatenate(
            [col.values, jnp.zeros((extra,), col.values.dtype)])
        validity = (
            jnp.concatenate([col.validity, jnp.zeros((extra,), jnp.bool_)])
            if col.validity is not None else None)
        cols.append(Column(vals, col.dtype, validity, col.dictionary))
    selection = jnp.concatenate(
        [batch.selection, jnp.zeros((extra,), jnp.bool_)])
    return ColumnBatch(batch.schema, cols, selection, batch.num_rows)


# compact_perm is kernels/search.py's first_live (whose _BLOCK, _TOP,
# _QUERY_CHUNK and _COUNT_ROW these are): the k-th live row by searching
# the running count, shared with the mesh exchange's pack.
compact_perm = first_live


def take_batch(batch: ColumnBatch, perm: jax.Array, live: jax.Array) -> ColumnBatch:
    """Reorder a batch by ``perm``; ``live`` is the selection after reorder."""
    cols = []
    for col in batch.columns:
        vals = jnp.take(col.values, perm, axis=0)
        validity = (
            jnp.take(col.validity, perm, axis=0) if col.validity is not None else None
        )
        cols.append(Column(vals, col.dtype, validity, col.dictionary))
    return ColumnBatch(
        batch.schema, cols, live, jnp.sum(live).astype(jnp.int32)
    )
