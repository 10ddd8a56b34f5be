"""Core physical operators: scan, filter, projection, merge, sort, limit,
repartition.

TPU-native equivalents of the reference's PhysicalPlanNode variants
CsvScan/ParquetScan/Filter/Projection/Merge/Sort/GlobalLimit/LocalLimit/
Repartition/CoalesceBatches (reference: rust/core/proto/ballista.proto:
294-312). Filter and Projection are PipelineOps — they fuse with adjacent
pipeline stages into a single XLA program (batches never round-trip to HBM
between them).
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnBatch
from ..compile import bucket_capacity, fingerprint, governed
from ..datatypes import Schema
from ..errors import ExecutionError, NotImplementedError_
from .. import expr as ex
from ..kernels.expr_eval import Evaluator
from ..kernels.sort import sort_permutation
from ..kernels.hashing import splitmix64
from ..logical import TableSource
from ..observability.tracing import trace_event, trace_span
from .base import (PhysicalPlan, PipelineOp, Partitioning, concat_batches,
                   pad_batch, side_name, take_batch)


def compute_partition_ids(batch: ColumnBatch, hash_exprs, num_partitions: int,
                          row_offset: int, evaluator: Evaluator):
    """int32 partition id per row: chained splitmix64 over the hash exprs,
    or round-robin by global row index. Shared by the in-process
    RepartitionExec and the executor's shuffle writes so both planes agree.

    utf8 keys hash their STRING VALUE (via per-dictionary stable FNV-1a
    hashes), never the dictionary code — codes are producer-local and would
    break hash co-location across independent producers."""
    if hash_exprs:
        h = jnp.zeros((batch.capacity,), jnp.uint64)
        for e in hash_exprs:
            r = evaluator.evaluate(e, batch)
            v = jnp.broadcast_to(r.values, (batch.capacity,))
            if r.dictionary is not None:
                str_hashes = jnp.asarray(r.dictionary.stable_hashes())
                v = jnp.take(str_hashes, v.astype(jnp.int32), mode="clip")
            h = splitmix64(h ^ splitmix64(v.astype(jnp.int64)))
        return (h % jnp.uint64(num_partitions)).astype(jnp.int32)
    idx = row_offset + jnp.arange(batch.capacity, dtype=jnp.int32)
    return idx % num_partitions


def shuffle_dest_program(schema: Schema, hash_exprs, num_partitions: int):
    """The governed program behind a shuffle write's ONE device step a
    batch: ``dest(batch, num_partitions, row_offset)`` is every row's
    destination as :func:`compute_partition_ids` gives it, with
    ``num_partitions`` for a dead row (dead rows sort last), in the
    narrowest unsigned type that holds ``num_partitions`` (one byte up
    to 255: the host's stable sort of it is a radix pass). Elementwise
    only. ``num_partitions`` and ``row_offset`` are operands, so one
    program a (schema, hash expressions, width) serves every fan-out the
    cost feedback moves through; jax specializes it a capacity."""
    dtype = next(t for t in (jnp.uint8, jnp.uint16, jnp.uint32)
                 if num_partitions <= jnp.iinfo(t).max)

    def build():
        ev = Evaluator(schema)

        def dest(batch: ColumnBatch, n_out, row_offset):
            pids = compute_partition_ids(batch, hash_exprs, n_out,
                                         row_offset, ev)
            return jnp.where(batch.selection, pids, n_out).astype(dtype)

        return dest

    return governed(("shuffle.dest", schema, fingerprint(hash_exprs),
                     jnp.dtype(dtype).name), build)


class ScanExec(PhysicalPlan):
    """Table scan over a partitioned source (reference: CsvScanExecNode /
    ParquetScanExecNode, ballista.proto:334-354).

    Execution rides the ingest pipeline (ballista_tpu/ingest): with
    ``BALLISTA_PREFETCH_BATCHES`` > 0 the source generator runs on a
    pool worker behind a bounded queue, so parse+H2D of chunk N+1
    overlaps the consumer's device compute on chunk N, and scans
    ``prime()``d ahead (client/executor collect paths) overlap each
    other cross-table. ``BALLISTA_PREFETCH_BATCHES=0`` restores the
    serial inline pull exactly."""

    def __init__(self, table_name: str, source: TableSource,
                 projection: Optional[Sequence[str]] = None):
        self.table_name = table_name
        self.source = source
        self.projection = tuple(projection) if projection is not None else None
        # partition -> live PrefetchHandle (primed ahead of execution);
        # the lock covers priming from the collect thread racing an
        # executor worker's execute()
        self._primed: dict = {}
        self._primed_lock = threading.Lock()

    def output_schema(self) -> Schema:
        s = self.source.table_schema()
        return s.project(self.projection) if self.projection else s

    def output_partitioning(self) -> Partitioning:
        return Partitioning("unknown", self.source.num_partitions())

    def with_new_children(self, children):
        assert not children
        return self

    def _recorder(self):
        from ..ingest.phases import PhaseRecorder
        from ..observability.metrics import metrics_enabled

        return PhaseRecorder(self.metrics() if metrics_enabled() else None)

    def _prefetchable(self, partition: int) -> bool:
        """False when there is no parse/H2D to overlap: memory-resident
        sources, cache sources already materialized for this
        (partition, projection), and device-resident partitions (table
        cache hit) — the warm path stays queue-free."""
        from ..io.cache import CacheSource
        from ..io.memory import MemTableSource

        src = self.source
        if isinstance(src, MemTableSource):
            return False
        if isinstance(src, CacheSource) and \
                src.is_materialized(partition, self.projection):
            return False
        is_resident = getattr(src, "is_resident", None)
        if is_resident is not None and is_resident(partition,
                                                   self.projection):
            return False
        return True

    def prime(self, partition: int):
        """Start background parse+H2D for one partition on the ingest
        pool (idempotent). Returns the handle, or None when the
        pipeline is gated off or there is nothing to overlap."""
        from ..ingest import prefetch_batches
        from ..ingest.pipeline import PrefetchHandle

        depth = prefetch_batches()
        if depth <= 0 or not self._prefetchable(partition):
            return None
        with self._primed_lock:
            h = self._primed.get(partition)
            if h is None:
                h = PrefetchHandle(
                    lambda p=partition: self.source.scan(p, self.projection),
                    depth,
                    label=f"{self.table_name}[{partition}]",
                    recorder=self._recorder(),
                )
                self._primed[partition] = h
        return h

    def cancel_primed(self) -> None:
        """Drop every unconsumed primed handle (plan abandoned or
        rewritten away): producers stop, queued batches release."""
        with self._primed_lock:
            handles, self._primed = list(self._primed.values()), {}
        for h in handles:
            h.cancel()

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        counts = []  # the batches' live rows, still on the device
        for batch in self._batches(partition):
            counts.append(batch.num_rows)
            yield batch
        self._note_served(partition, counts)

    def _batches(self, partition: int) -> Iterator[ColumnBatch]:
        from ..ingest import prefetch_batches
        from ..ingest.phases import bound_iter

        if prefetch_batches() > 0:
            self.prime(partition)  # no-op when nothing to overlap
        with self._primed_lock:
            handle = self._primed.pop(partition, None)
        if handle is None:  # pipeline off: the old serial pull loop
            yield from bound_iter(
                self.source.scan(partition, self.projection),
                self._recorder())
        else:
            try:
                yield from handle
            finally:
                # consumer may abandon the stream early (LimitExec):
                # stop the producer instead of leaving it blocked on a
                # full queue
                handle.cancel()

    def _note_served(self, partition: int, counts: list) -> None:
        """One ``scan.serve`` event a partition scanned to its end: how
        it was served (``resident``: from the device table cache;
        ``filled``: parsed and pinned there by this scan; ``streamed``:
        made for this scan alone) and its live ``rows``. A resident
        partition's rows are kept on the host beside its cache entry; a
        streamed one's are read here, a read that a parse dwarfs; a
        source outside the residency layer (a memory table) is not
        asked, so that no small query blocks for the sake of a count."""
        from ..cache.residency import process_table_cache
        from ..observability.metrics import metrics_enabled

        fn = getattr(self.source, "scan_cache_outcome", None)
        outcome = fn(partition) if fn is not None else None
        if outcome == "hit" and metrics_enabled():
            self.metrics().add_counter("table_cache_hits")
        rows = None
        if outcome in ("hit", "filled"):
            rows = process_table_cache().live_rows(
                self.source.residency_key(partition, self.projection))
        if rows is None and outcome is not None:
            with trace_span("device.block", site="scan.rows",
                            n=len(counts)):
                rows = int(sum(jax.device_get(counts)))
        trace_event("scan.serve", table=self.table_name, rows=rows,
                    batches=len(counts),
                    how={"hit": "resident",
                         "filled": "filled"}.get(outcome, "streamed"))

    def estimated_rows(self):
        return self.source.estimated_rows()

    def display(self) -> str:
        p = f" projection={list(self.projection)}" if self.projection else ""
        return f"ScanExec: {self.table_name}{p}"

    def pretty_metrics(self, indent: int = 0) -> str:
        """EXPLAIN ANALYZE line with the device-residency outcome of
        the latest scan(s) appended — deliberately NOT in display(),
        which feeds compile signatures and must stay run-invariant."""
        fn = getattr(self.source, "scan_cache_outcome", None)
        outcomes = set()
        if fn is not None:
            for p in range(self.source.num_partitions()):
                o = fn(p)
                if o is not None:
                    outcomes.add(o)
        cache_ann = (f" [cache: {'|'.join(sorted(outcomes))}]"
                     if outcomes else "")
        ann = self.metrics().summary()
        return ("  " * indent + self.display() + cache_ann
                + (f", metrics=[{ann}]" if ann else "") + "\n")


class FilterExec(PipelineOp):
    compactable = True  # kills rows: fused chain output is compacted

    def __init__(self, predicate: ex.Expr, child: PhysicalPlan):
        self.predicate = predicate
        self.child = child
        self._ev = Evaluator(child.output_schema())

    def _signature_parts(self) -> tuple:
        return (fingerprint(self.predicate), self.child.output_schema())

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def with_new_children(self, children):
        return FilterExec(self.predicate, children[0])

    def device_transform(self, batch: ColumnBatch) -> ColumnBatch:
        mask = self._ev.evaluate_predicate(self.predicate, batch)
        sel = jnp.logical_and(batch.selection, mask)
        return batch.with_selection(sel)

    def display(self) -> str:
        return f"FilterExec: {self.predicate.name()}"


class ProjectionExec(PipelineOp):
    def __init__(self, exprs: List[ex.Expr], child: PhysicalPlan):
        self.exprs = list(exprs)
        self.child = child
        self._in_schema = child.output_schema()
        self._ev = Evaluator(self._in_schema)
        self._schema = Schema([e.to_field(self._in_schema) for e in self.exprs])

    def _signature_parts(self) -> tuple:
        return (fingerprint(self.exprs), self._in_schema)

    def output_schema(self) -> Schema:
        return self._schema

    def with_new_children(self, children):
        return ProjectionExec(self.exprs, children[0])

    def device_transform(self, batch: ColumnBatch) -> ColumnBatch:
        cols = [self._ev.to_column(e, batch) for e in self.exprs]
        # trust planned schema for dtypes (evaluator agrees by construction)
        return batch.with_columns(self._schema, cols)

    def display(self) -> str:
        return f"ProjectionExec: {', '.join(e.name() for e in self.exprs)}"


class MergeExec(PhysicalPlan):
    """Gather all input partitions into one (reference: MergeExecNode,
    ballista.proto:409-413; planner boundary at planner.rs:136-148)."""

    def __init__(self, child: PhysicalPlan):
        self.child = child

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def output_partitioning(self) -> Partitioning:
        return Partitioning("unknown", 1)

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return MergeExec(children[0])

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        if partition != 0:
            raise ExecutionError("MergeExec has a single output partition")
        from ..ingest import iter_partitions

        # pipelined: child partitions (each a whole scan/join/partial-agg
        # subtree) produce concurrently on the ingest pool, merged in
        # partition order — the serial pull loop when gated off
        yield from iter_partitions(
            self.child,
            range(self.child.output_partitioning().num_partitions))

    def display(self) -> str:
        return "MergeExec"


class CoalesceBatchesExec(PhysicalPlan):
    """Concatenate a partition's batches into one device batch (reference:
    CoalesceBatchesExecNode, ballista.proto:362-368 — there it re-chunks
    small batches; here it feeds barrier ops one static-shape batch)."""

    def __init__(self, child: PhysicalPlan):
        self.child = child

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return CoalesceBatchesExec(children[0])

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        batches = list(self.child.execute(partition))
        if not batches:
            return
        yield concat_batches(self.output_schema(), batches)

    def display(self) -> str:
        return "CoalesceBatchesExec"


class SortExec(PhysicalPlan):
    """Total sort of a single partition (reference: SortExecNode,
    ballista.proto:424-431)."""

    def __init__(self, sort_exprs: List[ex.SortExpr], child: PhysicalPlan):
        self.sort_exprs = list(sort_exprs)
        self.child = child
        self._ev = Evaluator(child.output_schema())

    def _signature_parts(self) -> tuple:
        return (fingerprint(self.sort_exprs), self.child.output_schema())

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def output_partitioning(self) -> Partitioning:
        return Partitioning("unknown", 1)

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return SortExec(self.sort_exprs, children[0])

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        batches = list(self.child.execute(partition))
        if not batches:
            return
        batch = concat_batches(self.output_schema(), batches)

        def build():
            tw = self.trace_twin()  # don't pin the child subtree

            def do_sort(b: ColumnBatch) -> ColumnBatch:
                keys = []
                for se in tw.sort_exprs:
                    r = tw._ev.evaluate(se.expr, b)
                    v = jnp.broadcast_to(r.values, (b.capacity,))
                    keys.append((v, se.ascending))
                perm = sort_permutation(keys, b.selection)
                live_sorted = jnp.take(b.selection, perm)
                return take_batch(b, perm, live_sorted)

            return do_sort

        yield self.governed_jit(("sort.run",), build)(batch)

    def display(self) -> str:
        return f"SortExec: {', '.join(e.name() for e in self.sort_exprs)}"


class LimitExec(PhysicalPlan):
    """Take the first n live rows of a (single) partition (reference:
    GlobalLimitExecNode/LocalLimitExecNode, ballista.proto:386-397)."""

    def __init__(self, n: int, child: PhysicalPlan):
        self.n = n
        self.child = child

    def _signature_parts(self) -> tuple:
        return ()  # take_first is operator-independent (n is traced)

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return LimitExec(self.n, children[0])

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        remaining = self.n

        def build():
            def take_first(b: ColumnBatch, k) -> ColumnBatch:
                rank = jnp.cumsum(b.selection.astype(jnp.int32)) - 1
                sel = jnp.logical_and(b.selection, rank < k)
                return b.with_selection(sel)

            return take_first

        take = self.governed_jit(("limit.take",), build)
        for batch in self.child.execute(partition):
            if remaining <= 0:
                break
            out = take(batch, jnp.int32(remaining))
            remaining -= out.num_rows_host()
            yield out

    def display(self) -> str:
        return f"LimitExec: {self.n}"


class RepartitionExec(PhysicalPlan):
    """Re-partition input into N output partitions by hash or round-robin
    (reference: RepartitionExecNode, ballista.proto:415-422).

    Single-process implementation: child partitions are materialized once
    and each batch is sorted by destination once (``repart.materialize``);
    an output partition then gathers its rows out of every source batch
    into one compacted batch (``repart.take``, one program a source). A
    cached plan keeps the sorted sources between executions
    (``repart.reused``), so a warm query runs only the takes. The
    distributed path uses shuffle writes instead.
    """

    def __init__(self, child: PhysicalPlan, num_partitions: int,
                 hash_exprs: Optional[List[ex.Expr]] = None):
        self.child = child
        self.num_partitions = num_partitions
        self.hash_exprs = hash_exprs
        self._ev = Evaluator(child.output_schema())
        self._cache: Optional[List[ColumnBatch]] = None
        # concurrent partition execution (ingest iter_partitions, the
        # cluster analogue of which is per-task plan instances) must
        # materialize exactly once; RLock: _materialize_parts calls
        # _materialize
        self._mat_lock = threading.RLock()

    def _signature_parts(self) -> tuple:
        return (self.num_partitions, fingerprint(self.hash_exprs),
                self.child.output_schema())

    def _detach(self) -> None:
        super()._detach()
        self._cache = None
        self._parts = None  # materialized batches must not be pinned

    def output_schema(self) -> Schema:
        return self.child.output_schema()

    def output_partitioning(self) -> Partitioning:
        kind = "hash" if self.hash_exprs else "round_robin"
        cols = tuple(e.name() for e in (self.hash_exprs or []))
        return Partitioning(kind, self.num_partitions, cols)

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return RepartitionExec(children[0], self.num_partitions, self.hash_exprs)

    def partition_ids(self, batch: ColumnBatch, row_offset: int) -> jax.Array:
        """int32 partition id per row (traced)."""
        return compute_partition_ids(batch, self.hash_exprs,
                                     self.num_partitions, row_offset,
                                     self._ev)

    def _materialize(self) -> List[ColumnBatch]:
        with self._mat_lock:
            if self._cache is None:
                from ..ingest import iter_partitions

                self._cache = list(iter_partitions(
                    self.child,
                    range(self.child.output_partitioning()
                          .num_partitions)))
            return self._cache

    def _materialize_parts(self):
        """Materialize once and sort each batch by destination partition
        ONCE (not once per output partition): partition p is then a
        contiguous slice of the permutation. [(batch, perm, counts)],
        kept for as long as the plan lives."""
        if getattr(self, "_parts", None) is not None:
            trace_event("repart.reused")
            return self._parts
        with self._mat_lock:
            if getattr(self, "_parts", None) is None:
                with trace_span("repart.materialize",
                                side=side_name(self.child)) as span:
                    parts = self._sorted_sources()
                    span.attrs.update(
                        sources=len(parts),
                        rows=int(sum(c.sum() for _, _, c in parts)))
                self._parts = parts
            return self._parts

    def _sorted_sources(self):
        """[(source batch, its rows' permutation by destination, rows a
        destination)]: one ``lax.sort`` a child batch.

        With the ingest pipeline on, the per-batch host syncs are
        DEFERRED: every batch's sort is dispatched back-to-back and the
        count scalars resolve in one ``jax.device_get`` at the end, so
        the device never waits on the host between batches (hash
        repartitions don't read the row offset at all — only
        round-robin does, and it needs the per-batch row count on
        host). ``BALLISTA_PREFETCH_BATCHES=0`` restores the serial
        sync-per-batch loop."""
        from ..ingest import prefetch_batches

        def build():
            tw = self.trace_twin()  # don't pin materialized batches
            n_out = tw.num_partitions

            def sort_by_pid(b: ColumnBatch, offset):
                pids = tw.partition_ids(b, offset)
                d = jnp.where(b.selection, pids, n_out)  # dead last
                idx = jnp.arange(b.capacity, dtype=jnp.int32)
                _, perm = jax.lax.sort((d, idx), num_keys=1,
                                       is_stable=True)
                counts = jnp.bincount(d, length=n_out + 1)[:n_out]
                return perm, counts

            return sort_by_pid

        mask_fn = self.governed_jit(("repart.sort_by_pid",), build)
        batches = self._materialize()
        if prefetch_batches() > 0 and self.hash_exprs:
            from ..ingest import parallel_map

            # offset is unread by hash partitioning, so batches are
            # independent: the first sorts inline (the governed
            # entry traces exactly once), the rest dispatch from
            # pool workers — independent XLA executions genuinely
            # overlap across cores — and every count scalar
            # resolves in ONE device_get
            zero = jnp.int32(0)
            pairs = ([mask_fn(batches[0], zero)] if batches else [])
            pairs += parallel_map(lambda b: mask_fn(b, zero),
                                  batches[1:])
            with trace_span("device.block", site="repart.counts",
                            n=len(pairs)):
                resolved = jax.device_get([c for _, c in pairs])
            return [(b, perm, np.asarray(c))
                    for b, (perm, _), c in zip(batches, pairs, resolved)]
        parts = []
        offset = 0
        for batch in batches:
            perm, counts = mask_fn(batch, jnp.int32(offset))
            # offset-dependent batches serialize: one sync per
            # batch, each attributed to the blocked lane
            with trace_span("device.block", site="repart.counts", n=1):
                host_counts = np.asarray(counts)
            parts.append((batch, perm, host_counts))
            offset += batch.num_rows_host()
        return parts

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        """Yields ONE COMPACTED batch: rows of the requested partition are
        gathered to the front of a capacity that fits, so a partitioned
        consumer (e.g. a co-partitioned join) does 1/N the work per
        partition instead of re-touching full-capacity masked batches.
        Per-source fragments are coalesced so a multi-file scan times N
        buckets doesn't fan out into source*N fragments, each paying
        per-batch dispatch and assembly downstream. Mirrors the
        distributed path, where shuffle files are mask-compacted on IPC
        write."""
        yield from self._execute_fragments(partition, 0, None)

    def execute_fragments(self, partition: int, frag_lo: int,
                          frag_hi: int) -> Iterator[ColumnBatch]:
        """``execute(partition)`` restricted to source fragments
        ``[frag_lo, frag_hi)`` — the read unit standalone adaptive skew
        splitting carves a heavy partition by (fragments play the role
        shuffle producers play in the cluster path)."""
        yield from self._execute_fragments(partition, frag_lo, frag_hi)

    def num_fragments(self) -> int:
        return len(self._materialize_parts())

    def observed_partition_rows(self):
        """Post-materialization row histogram: ``(rows_per_partition,
        rows[partition][fragment])`` — the standalone stand-in for the
        cluster's shuffle byte histogram (bytes = rows x schema row
        width, estimated by the caller)."""
        parts = self._materialize_parts()
        per = [[int(counts[q]) for _, _, counts in parts]
               for q in range(self.num_partitions)]
        return [sum(row) for row in per], per

    def _execute_fragments(self, partition: int, frag_lo: int,
                           frag_hi) -> Iterator[ColumnBatch]:
        pieces, rows = [], 0
        for batch, perm, counts in self._materialize_parts()[
                frag_lo:frag_hi]:
            n = int(counts[partition])
            start = int(counts[:partition].sum())
            # never exceed the source capacity: a longer slice would
            # silently clamp. Bucketed, so unevenly-filled output
            # partitions land on the canonical ladder
            cap = min(bucket_capacity(n), batch.capacity)
            idx = perm[start:start + cap]
            if int(idx.shape[0]) < cap:  # tail partition: pad the gather
                idx = jnp.pad(idx, (0, cap - int(idx.shape[0])))

            def build(_cap=cap):
                def take_front(b, idx, n):
                    live = jnp.arange(_cap, dtype=jnp.int32) < n
                    return take_batch(b, idx, live)

                return take_front

            take = self.governed_jit(("repart.take", cap), build)
            pieces.append(take(batch, idx, jnp.int32(n)))
            rows += n
        if not pieces:
            return
        out = pieces[0]
        if len(pieces) > 1:
            out = concat_batches(self.output_schema(), pieces)
            # concat of ladder-sized pieces isn't itself a ladder rung
            # (128+64=192); pad up so downstream per-capacity jit caches
            # reuse one compiled program across output partitions
            target = bucket_capacity(out.capacity)
            if target != out.capacity:
                out = pad_batch(out, target)
        trace_event("repart.take", side=side_name(self.child), rows=rows,
                    pieces=len(pieces), capacity=out.capacity,
                    cols=len(out.columns))
        yield out

    def display(self) -> str:
        k = "hash" if self.hash_exprs else "round-robin"
        return f"RepartitionExec: {k} into {self.num_partitions}"


class EmptyExec(PhysicalPlan):
    """Zero- or one-row empty relation (reference: EmptyExecNode,
    ballista.proto:356-360)."""

    def __init__(self, produce_one_row: bool = False):
        self.produce_one_row = produce_one_row

    def output_schema(self) -> Schema:
        return Schema([])

    def with_new_children(self, children):
        return self

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        n = 1 if self.produce_one_row else 0
        sel = np.zeros(8, dtype=bool)
        sel[:n] = True
        yield ColumnBatch(
            Schema([]), [], jnp.asarray(sel), jnp.asarray(np.int32(n))
        )

    def display(self) -> str:
        return "EmptyExec"
