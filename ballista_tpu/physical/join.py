"""Join physical operator.

TPU-native equivalent of the reference's ``HashJoinExec`` (reference:
rust/core/proto/ballista.proto:399-407; the distributed planner passes join
children through without a co-partition stage, rust/scheduler/src/
planner.rs:172-173 — we do the same in round 1, with the build side merged
to a single partition).

The build (left) side is materialized once and sorted (kernels.join);
probe-side batches stream through a jitted probe that appends gathered
build columns (a batch whose fused probe-side filter kept under a quarter
of it is compacted first, ``_probe_inputs``). FK->PK joins (unique build keys) take the no-expansion fast
path; duplicate build keys fall back to the expanding probe, which counts
its matches first and expands them at the capacity the count names.

Join types: inner, left (preserves PROBE side — the planner picks which
logical side becomes the probe accordingly), semi, anti, and full (a
probe-preserving pass plus one batch of unmatched build rows).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnBatch, round_capacity
from ..compile import bucket_capacity, governed
from ..datatypes import Schema
from ..errors import ExecutionError, NotImplementedError_
from ..kernels import join as join_k
from ..kernels.search import depth as search_depth
from ..observability.metrics import metrics_enabled
from ..observability.tracing import trace_event, trace_span
from .base import (PhysicalPlan, Partitioning, concat_batches,
                   side_name)

JOIN_TYPES = ("inner", "left", "semi", "anti", "full")

# Deferred-sync window of ``_probe_expand_stream``: match totals of this
# many probe batches are fetched in one ``device_get``. The window also
# bounds BYTES pinned on device (a probe batch and its match ranges stay
# live until its total is fetched), so a join over huge batch capacities
# flushes early instead of multiplying its peak memory by the
# batch-count window.
_SYNC_WINDOW = 8
_SYNC_WINDOW_BYTES = 1 << 30


def _note_search(table, pb: ColumnBatch) -> None:
    """One probe batch goes through the stepped search of the sorted
    build keys (``kernels/search.py``); the dense table's does not."""
    build = table.sorted_keys.shape[0]
    trace_event("join.search", probes=pb.capacity, build=build,
                levels=search_depth(build))


def _chained_key(chained: bool) -> tuple:
    """Key suffix of a probe program traced WITHOUT the fused probe
    chain (its batch arrived chained and compacted). Empty otherwise, so
    the fused programs keep the keys they always had."""
    return ("chained",) if chained else ()


class JoinExec(PhysicalPlan):
    """build = left child (merged to 1 partition), probe = right child."""

    def __init__(
        self,
        build: PhysicalPlan,
        probe: PhysicalPlan,
        on: List[Tuple[str, str]],  # (build_col, probe_col)
        how: str = "inner",
        null_aware: bool = False,
        partitioned: bool = False,
        adaptive_note: Optional[str] = None,
        probe_chain: Optional[List] = None,
        probe_key_raw: Optional[dict] = None,
        out_columns: Optional[Tuple[str, ...]] = None,
    ):
        if how not in JOIN_TYPES:
            raise NotImplementedError_(f"join type {how}")
        if not on:
            raise NotImplementedError_("joins require at least one key")
        self.build = build
        self.probe = probe
        self.on = list(on)
        self.how = how
        self.null_aware = null_aware  # SQL NOT IN anti-join semantics
        # partitioned: both children are hash-partitioned on the join keys
        # with the SAME partition count/hash (the planner wraps them in
        # RepartitionExec), so partition p joins build[p] x probe[p] and
        # the build side never merges across partitions. Beats the
        # reference, which always passes join children through unsplit
        # (reference: rust/scheduler/src/planner.rs:172-173).
        self.partitioned = partitioned
        # set when adaptive execution rewrote this join (EXPLAIN surface)
        self.adaptive_note = adaptive_note
        # whole-stage fusion (physical/fusion.py): the Filter/Projection
        # chain that used to feed the probe side, applied INSIDE every
        # traced probe program instead of as a separate per-batch jit
        # (unless it kills most rows: ``_probe_inputs`` then runs it
        # alone and compacts before the probe). When set, ``probe`` is the chain's SOURCE; ``probe_key_raw``
        # maps each post-chain probe key column name to its raw source
        # column (for the host-side dictionary remap).
        self.probe_chain = tuple(probe_chain or ())
        self.probe_key_raw = dict(probe_key_raw or {})
        # the columns this join EMITS, in output order (the logical
        # join's pruned schema, ``optimizer.prune_columns``): assembly
        # gathers one column a field of ``output_schema()``, so a join
        # key or a filter's column that nothing above reads costs no
        # gather. None = every build field, then the probe's (a list
        # that says just that is kept as None). The inputs are not
        # narrowed. Semi/anti joins emit the probe batch under a
        # selection and take no list
        self.out_columns = None
        if out_columns is not None and how not in ("semi", "anti") \
                and tuple(out_columns) != self.output_schema().names():
            self.out_columns = tuple(out_columns)
        # partition -> (table, batch, unique, has_null, key mode,
        #               codec tables, build keys, build live)
        self._build_data = {}
        self._remap_cache = {}
        # concurrent partition execution (ingest iter_partitions): a
        # merged build is shared by every partition (key 0) and must
        # materialize exactly once — the heavy device work makes this
        # NOT a benign race. Per-KEY locks so a partitioned join's
        # independent per-partition builds still overlap.
        from ..ingest import KeyedLocks

        self._build_locks = KeyedLocks()

    def _signature_parts(self) -> tuple:
        # partitioned/adaptive_note steer HOST orchestration only — no
        # traced closure reads them, so a demoted (adaptive) join reuses
        # the original join's compiled probes. A fused probe chain IS
        # traced, so its signatures ride the key, and so does what the
        # assembly emits: two joins over equal inputs that emit different
        # columns never share a program.
        return (self.how, tuple(self.on), self.null_aware,
                self.build.output_schema(), self._probe_out_schema(),
                tuple(op.compile_signature() for op in self.probe_chain),
                self.out_columns)

    def _probe_out_schema(self) -> Schema:
        """Schema of probe batches AFTER the fused chain (equals the
        probe child's schema when nothing is fused)."""
        if self.probe_chain:
            return self.probe_chain[-1].output_schema()
        return self.probe.output_schema()

    def _probe_prologue(self, pb: ColumnBatch) -> ColumnBatch:
        """Fused probe-side chain (innermost first). Traced."""
        for op in self.probe_chain:
            pb = op.device_transform(pb)
        return pb

    def _detach(self) -> None:
        from .base import SchemaLeaf

        self.build = SchemaLeaf(self.build.output_schema())
        self.probe = SchemaLeaf(self.probe.output_schema())
        self.probe_chain = tuple(op.trace_twin()
                                 for op in self.probe_chain)
        self._build_data = {}   # materialized build-side device buffers
        self._remap_cache = {}  # per-query dictionaries

    # -- composite keys ------------------------------------------------------
    #
    # Three representations, picked at build materialization:
    #   "raw"    1 key column: its int64 values, exact.
    #   "packed" 2 key columns within 31/32-bit ranges: (a << 32) | b.
    #   "codec"  anything else: each key column is iteratively RANKED
    #            against the (sorted) build side and packed with the
    #            running code, which is re-ranked back under the build
    #            capacity — exact for any number/width of key columns
    #            (no hash collisions), static shapes, ~2 sorts per extra
    #            column. Probe rows ride the same tables; a probe value
    #            absent from the build fails its exactness check and can
    #            never collide into a live build code.

    def _key_of(self, batch: ColumnBatch, cols: List[str]):
        """raw/packed representations (codec handled separately)."""
        first = batch.column(cols[0])
        keys = first.values.astype(jnp.int64)
        live_ext = first.validity
        if len(cols) == 2:
            second = batch.column(cols[1])
            keys = (keys << 32) | (second.values.astype(jnp.int64)
                                   & jnp.int64(0xFFFFFFFF))
            if second.validity is not None:
                live_ext = (
                    second.validity if live_ext is None
                    else jnp.logical_and(live_ext, second.validity)
                )
        return keys, live_ext

    # Dense direct-index mode limits: table entries are int32 rows; cap
    # the table at 16M entries (64 MB HBM) and at 8x the build capacity
    # so pathological sparse keys (e.g. hash-like ids) stay on the
    # sorted path.
    _DENSE_MAX_SIZE = 1 << 24
    _DENSE_FACTOR = 8

    def _build_stats(self, bb: ColumnBatch, cols: List[str]):
        """ONE jitted program -> (host scalars, device live mask): per-col
        min/max over selected rows, live-key min/max for the first col,
        null-key flag. Only the scalars cross to host — replaces the old
        host-side full-column pulls, which over a slow host<->device link
        cost more than the join itself. The combined live mask stays on
        device for the build to reuse (it is exactly the
        selection & key-validity reduction the raw/packed paths need)."""

        tw = self.trace_twin()

        def stats(bb):
            live_ext = tw._key_live_ext(bb, cols)
            live = bb.selection
            if live_ext is not None:
                live = jnp.logical_and(live, live_ext)
                has_null = jnp.any(jnp.logical_and(
                    bb.selection, jnp.logical_not(live_ext)))
            else:
                has_null = jnp.asarray(False)
            out = {"has_null": has_null,
                   "nlive": jnp.sum(live.astype(jnp.int32))}
            maxi = jnp.iinfo(jnp.int64).max
            for i, c in enumerate(cols):
                v = bb.column(c).values.astype(jnp.int64)
                out[f"sel_min_{i}"] = jnp.min(
                    jnp.where(bb.selection, v, maxi))
                out[f"sel_max_{i}"] = jnp.max(
                    jnp.where(bb.selection, v, -maxi))
            v0 = bb.column(cols[0]).values.astype(jnp.int64)
            out["live_min"] = jnp.min(jnp.where(live, v0, maxi))
            out["live_max"] = jnp.max(jnp.where(live, v0, -maxi))
            return out, live

        fn = self.governed_jit(("join.stats",), lambda: stats)
        scalars, live = fn(bb)
        from ..observability import trace_span

        with trace_span("device.block", site="join.stats"):
            return jax.device_get(scalars), live

    def _pick_mode(self, stats, ncols: int) -> str:
        if ncols == 1:
            return "raw"
        if ncols > 2:
            return "codec"  # codec handles any column count
        amin, amax = int(stats["sel_min_0"]), int(stats["sel_max_0"])
        bmin, bmax = int(stats["sel_min_1"]), int(stats["sel_max_1"])
        if amin > amax:
            return "packed"  # no selected rows: any representation works
        packable = (max(abs(amin), abs(amax)) < (1 << 31)
                    and bmin >= 0 and bmax < (1 << 32) - 1)
        return "packed" if packable else "codec"

    def _key_live_ext(self, batch: ColumnBatch, cols: List[str]):
        live_ext = None
        for c in cols:
            v = batch.column(c).validity
            if v is not None:
                live_ext = v if live_ext is None else jnp.logical_and(
                    live_ext, v)
        return live_ext

    def _codec_build(self, bb: ColumnBatch, cols: List[str]):
        """(codes, live, tables) for the build side. Traced."""
        live_ext = self._key_live_ext(bb, cols)
        live = bb.selection
        if live_ext is not None:
            live = jnp.logical_and(live, live_ext)
        nlive = jnp.sum(live.astype(jnp.int32))
        cap = bb.capacity
        maxi = jnp.iinfo(jnp.int64).max
        tables = []
        code = None
        for c in cols:
            v = bb.column(c).values.astype(jnp.int64)
            sv = jnp.sort(jnp.where(live, v, maxi))
            r = jnp.searchsorted(sv, v).astype(jnp.int64)
            if code is None:
                code = r
                tables.append((sv, None))
            else:
                combined = code * (cap + 1) + r
                sc = jnp.sort(jnp.where(live, combined, maxi))
                code = jnp.searchsorted(sc, combined).astype(jnp.int64)
                tables.append((sv, sc))
        return code, live, (tuple(tables), nlive)

    def _codec_probe(self, vals, tables, nlive):
        """(codes, exact mask) for probe key value arrays using the
        build's rank tables. Traced."""
        exact = jnp.ones(vals[0].shape, jnp.bool_)
        cap = tables[0][0].shape[0]
        code = None
        for v, (sv, sc) in zip(vals, tables):
            r = jnp.searchsorted(sv, v).astype(jnp.int64)
            hit = jnp.take(sv, jnp.minimum(r, cap - 1)) == v
            exact = jnp.logical_and(exact,
                                    jnp.logical_and(r < nlive, hit))
            if code is None:
                code = r
            else:
                combined = code * (cap + 1) + r
                rc = jnp.searchsorted(sc, combined).astype(jnp.int64)
                hitc = jnp.take(sc, jnp.minimum(rc, cap - 1)) == combined
                exact = jnp.logical_and(exact,
                                        jnp.logical_and(rc < nlive, hitc))
                code = rc
        return code, exact

    # -- schema -------------------------------------------------------------

    def output_schema(self) -> Schema:
        bs, ps = self.build.output_schema(), self._probe_out_schema()
        if self.how in ("semi", "anti"):
            return ps
        seen = {f.name for f in bs.fields}
        extra = [f for f in ps.fields if f.name not in seen]
        # build fields become nullable under probe-preserving (left) joins
        full = Schema(list(bs.fields) + extra)
        if self.out_columns is None:
            return full
        return full.project(self.out_columns)

    def estimated_rows(self):
        """Semi/anti joins emit a SUBSET of the probe side — the base
        sum-of-children over-estimate would also count the membership
        list, inflating a pruned side enough to flip cost-based
        orientation the wrong way (q18's IN-subquery side estimated
        above the full lineitem scan)."""
        if self.how in ("semi", "anti"):
            return self.probe.estimated_rows()
        return super().estimated_rows()

    def output_partitioning(self) -> Partitioning:
        if self.how == "full":
            # one task streams every probe partition and appends the
            # unmatched build rows (needs the global build-hit bitmap)
            return Partitioning("unknown", 1)
        return self.probe.output_partitioning()

    def children(self):
        return [self.build, self.probe]

    def with_new_children(self, children):
        return JoinExec(children[0], children[1], self.on, self.how,
                        self.null_aware, self.partitioned,
                        self.adaptive_note, list(self.probe_chain),
                        self.probe_key_raw, self.out_columns)

    def display(self) -> str:
        on = ", ".join(f"{l}={r}" for l, r in self.on)
        part = " partitioned" if self.partitioned else ""
        note = f" [adaptive: {self.adaptive_note}]" if self.adaptive_note \
            else ""
        fused = ""
        if self.probe_chain:
            ops = "→".join(type(op).__name__.replace("Exec", "")
                           for op in self.probe_chain)
            fused = f" [fused probe: {ops}]"
        return (f"JoinExec: how={self.how} on=[{on}]{self._out_label()}"
                f"{part}{note}{fused}")

    def _out_label(self) -> str:
        """`` out=[...]`` for plan text when the join emits a list."""
        if self.out_columns is None:
            return ""
        return f" out=[{', '.join(self.out_columns)}]"

    # -- execution ----------------------------------------------------------

    def _empty_build_batch(self) -> ColumnBatch:
        """All-dead build batch for legitimately empty hash partitions."""
        from ..columnar import empty_batch

        return empty_batch(self.build.output_schema())

    def _materialize_build(self, partition: int = 0):
        """The build side of ``partition`` (of the whole join unless
        ``partitioned``), built once: a cached plan keeps it between
        executions, so a later call (``join.build_reused``) runs nothing
        of the build subtree."""
        key = partition if self.partitioned else 0
        if key not in self._build_data:  # fast path, no lock once built
            with self._build_locks.get(key):
                if key not in self._build_data:
                    with trace_span("join.build", side=side_name(self.build),
                                    partitioned=self.partitioned) as span:
                        self._build_data[key] = self._build_side(
                            partition, span.attrs)
                    return self._build_data[key]
        trace_event("join.build_reused", partitioned=self.partitioned)
        return self._build_data[key]

    def _build_side(self, partition: int, note: dict):
        """Run the build subtree and make its lookup table; ``note``
        takes what was built (the ``join.build`` span's attributes, all
        of them host values the build has read anyway)."""
        if self.partitioned:
            batches = list(self.build.execute(partition))
        else:
            from ..ingest import iter_partitions

            batches = list(iter_partitions(
                self.build,
                range(self.build.output_partitioning().num_partitions)))
        if not batches:
            if self.partitioned:  # a hash partition may be empty
                batches = [self._empty_build_batch()]
            else:
                raise ExecutionError("join build side produced no batches")
        bb = concat_batches(self.build.output_schema(), batches)
        bcols = [b for b, _ in self.on]
        stats, stats_live = self._build_stats(bb, bcols)
        has_null_key = bool(stats["has_null"])
        nlive = int(stats["nlive"])
        mode = self._pick_mode(stats, len(bcols))
        if mode in ("raw", "packed"):
            keys, _ = self._key_of(bb, bcols)
            live = stats_live
            key_tables = ()
        else:
            codec_fn = self.governed_jit(
                ("join.codec_build",),
                lambda: (lambda b, _tw=self.trace_twin():
                         _tw._codec_build(b, bcols)))
            keys, live, key_tables = codec_fn(bb)
        table = None
        unique = True
        if mode == "raw" and nlive > 0:
            base = int(stats["live_min"])
            size = int(stats["live_max"]) - base + 1
            if 0 < size <= min(self._DENSE_MAX_SIZE,
                               self._DENSE_FACTOR * bb.capacity):
                # quantize the (static) table size so successive builds
                # with different key ranges reuse one compiled program;
                # padding slots stay -1 and can never match
                size = round_capacity(size)
                # operator-independent kernel: key WITHOUT the join
                # signature so every join shares one compiled entry
                # (metrics still bind to this operator)
                dense_fn = governed(
                    ("join.dense",), lambda: join_k.build_dense,
                    metrics=self.metrics() if metrics_enabled() else None,
                    jit_kwargs={"static_argnames": ("size",)})
                rows, dup = dense_fn(keys, live, jnp.int64(base), size=size)
                if not bool(dup):
                    table = join_k.BuildTable(
                        sorted_keys=None, order=None,
                        num_live=jnp.asarray(nlive, jnp.int32),
                        dense_rows=rows, dense_base=jnp.int64(base))
        if table is None:
            sorted_fn = governed(
                ("join.sorted",), lambda: join_k.build_sorted_with_unique,
                metrics=self.metrics() if metrics_enabled() else None)
            table, uniq = sorted_fn(keys, live)
            unique = bool(uniq)
        note.update(rows=nlive, capacity=bb.capacity,
                    out=len(self.output_schema().fields),
                    pieces=len(batches),
                    mode="sorted" if table.sorted_keys is not None
                    else "dense", unique=unique)
        return (table, bb, unique, has_null_key, mode, key_tables, keys,
                live)

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        (table, build_batch, unique, has_null_key, mode, key_tables,
         bkeys, blive) = self._materialize_build(partition)
        if self.how == "full":
            if partition != 0:
                raise ExecutionError("full outer join has a single partition")
            yield from self._execute_full(table, build_batch, unique,
                                          mode, key_tables, bkeys, blive)
            return
        if self.how == "anti" and self.null_aware and has_null_key:
            # SQL NOT IN with a NULL in the subquery: predicate is never
            # true -> empty result
            if self.probe_chain:
                # raw probe batches carry the SOURCE schema; emit one
                # all-dead batch of the (post-chain) output schema
                from ..columnar import empty_batch

                yield empty_batch(self.output_schema())
                return
            for pb in self.probe.execute(partition):
                yield pb.with_selection(
                    jnp.zeros((pb.capacity,), jnp.bool_)
                )
            return
        from .base import maybe_compact

        inputs = self._probe_inputs(build_batch,
                                    self.probe.execute(partition))
        if unique or self.how in ("semi", "anti"):
            # membership only needs the unique probe whatever the build's
            # duplicates. Selective joins (q16's NOT IN keeps ~15% of
            # partsupp) strand few live rows in huge batches; compacting
            # shrinks every downstream operator. A batch compacted BEFORE
            # its probe is not asked again: the probe can only remove
            # rows from a batch already sized to its survivors
            for pb, remaps, chained in inputs:
                out = self._probe_unique_batch(
                    table, build_batch, pb, mode, key_tables, remaps,
                    chained)
                yield out if chained else maybe_compact(out)
        else:
            yield from self._probe_expand_stream(
                table, build_batch, inputs, mode, key_tables)

    def _probe_inputs(self, build_batch: ColumnBatch, probe_iter):
        """``(probe batch, remaps, chained)`` for every batch of the
        probe side: where the probe batch is taken, for the unique,
        semi/anti and expanding forms alike.

        A fused probe chain that kills rows (a FilterExec) would make
        the probe gather build rows for every dead row of the capacity,
        so while the operator is still learning
        (``PhysicalPlan.still_compacting``, the rule ``PipelineOp.execute``
        follows) the chain runs FIRST as its own small program
        (``join.prologue``), its survivors are counted, and a batch under
        a quarter full is compacted before the probe: ``chained`` is then
        True and the probe programs skip the chain (q14: 1<<20 rows ->
        16,384, one ``join.probe_compacted`` event). A batch that declines,
        and every batch once two in a row have, goes to the probe RAW with
        ``chained`` False: the single fused program, no count read
        (``join.probe_fused``)."""
        asks = any(op.compactable for op in self.probe_chain)
        for pb in probe_iter:
            # raw batch: key columns under their pre-chain names
            remaps = self._remaps_for(build_batch, pb)
            if asks and self.still_compacting():
                kept = self._run_probe_chain(pb)
                small = self.compact_learning(kept)
                if small is not kept:
                    trace_event("join.probe_compacted",
                                rows=int(kept.num_rows),  # already read
                                capacity=pb.capacity, to=small.capacity)
                    yield small, remaps, True
                    continue
            if self.probe_chain:
                trace_event("join.probe_fused")
            yield pb, remaps, False

    def _run_probe_chain(self, pb: ColumnBatch) -> ColumnBatch:
        """The fused probe chain alone, as its own governed program."""
        def build():
            tw = self.trace_twin()
            return tw._probe_prologue

        return self.governed_jit(("join.prologue",), build)(pb)

    # full outer ------------------------------------------------------------

    def _execute_full(self, table, build_batch, unique, mode, key_tables,
                      bkeys, blive):
        """Probe-preserving (left) pass over every probe partition while
        accumulating which build rows matched, then one extra batch of
        unmatched build rows with null probe columns. The reference's
        DataFrame layer left joins as a TODO entirely
        (rust/client/src/context.rs:287-290)."""
        hit = np.zeros(build_batch.capacity, bool)
        nparts = self.probe.output_partitioning().num_partitions
        for p in range(nparts):
            for pb in self.probe.execute(p):
                remaps = self._remaps_for(build_batch, pb)
                if unique:
                    yield self._probe_unique_batch(table, build_batch, pb,
                                                   mode, key_tables, remaps)
                else:
                    yield from self._probe_expand_stream(
                        table, build_batch, iter([(pb, remaps, False)]),
                        mode, key_tables)
                hit |= np.asarray(self._mark_hits(build_batch, pb, mode,
                                                  key_tables, remaps,
                                                  bkeys, blive))
        # selection, not blive: build rows with NULL join keys can never
        # match but SQL still emits them with null probe columns
        from ..observability import trace_span

        with trace_span("device.block", site="join.unmatched"):
            unmatched = np.asarray(build_batch.selection) & ~hit
        yield self._unmatched_build_batch(build_batch, jnp.asarray(unmatched))

    def _mark_hits(self, build_batch, pb, mode, key_tables, remaps,
                   bkeys, blive):
        """bool [build_cap]: build rows whose key appears among this probe
        batch's live keys (reverse membership probe; duplicates fine).
        NOTE: redoes the probe-key extraction the main pass already did;
        folding a build_rows scatter into the probe jits would halve the
        full-join probe cost if it ever shows up in profiles."""
        def build():
            tw = self.trace_twin()

            def run(pb, key_tables, remaps, bkeys, blive):
                pb = tw._probe_prologue(pb)
                pkeys, plive = tw._probe_keys(pb, mode, key_tables, remaps)
                pt = join_k.build_lookup(pkeys, plive)
                _, matched = join_k.probe_unique(pt, bkeys, blive)
                return jnp.logical_and(blive, matched)

            return run

        fn = self.governed_jit(("join.mark", mode), build)
        return fn(pb, key_tables, remaps, bkeys, blive)

    def _unmatched_build_batch(self, bb: ColumnBatch,
                               unmatched) -> ColumnBatch:
        from ..columnar import Dictionary

        schema = self.output_schema()
        ps = self._probe_out_schema()
        cols = []
        for f in schema.fields:
            if bb.schema.has_field(f.name):
                cols.append(bb.column(f.name))
            else:  # probe-only column: all-NULL
                dt = ps.field(f.name).dtype
                d = Dictionary([]) if dt.kind == "utf8" else None
                cols.append(Column(
                    jnp.zeros((bb.capacity,), dt.device_dtype()), dt,
                    jnp.zeros((bb.capacity,), jnp.bool_), d,
                ))
        return ColumnBatch(schema, cols, unmatched,
                           jnp.sum(unmatched).astype(jnp.int32))

    # fast path: unique build keys ------------------------------------------

    def _probe_col_values(self, pb: ColumnBatch, pcol: str, remap):
        """Probe key column as int64 values + validity; utf8 codes are
        remapped into the BUILD dictionary's code space (codes are
        producer-local; comparing them across tables would be wrong).
        Probe strings absent from the build dictionary map to -1 ->
        invalid (they cannot match anything)."""
        c = pb.column(pcol)
        v = c.values.astype(jnp.int64)
        valid = c.validity
        if remap is not None:
            idx = jnp.clip(v, 0, remap.shape[0] - 1).astype(jnp.int32)
            v2 = jnp.take(remap, idx)
            miss = v2 < 0
            valid = (
                jnp.logical_not(miss) if valid is None
                else jnp.logical_and(valid, jnp.logical_not(miss))
            )
            v = jnp.where(miss, jnp.int64(0), v2)
        return v, valid

    def _probe_keys(self, pb: ColumnBatch, mode: str, key_tables, remaps):
        # mode is static (baked into the jit cache key); key_tables and
        # remaps are traced arguments so per-partition builds / per-source
        # dictionaries don't leak into the cached traces as constants
        pcols = [p for _, p in self.on]
        vals = []
        valid_all = None
        for pcol, remap in zip(pcols, remaps):
            v, valid = self._probe_col_values(pb, pcol, remap)
            vals.append(v)
            if valid is not None:
                valid_all = (
                    valid if valid_all is None
                    else jnp.logical_and(valid_all, valid)
                )
        plive = pb.selection
        if valid_all is not None:
            plive = jnp.logical_and(plive, valid_all)
        if mode == "codec":
            tables, nlive = key_tables
            pkeys, exact = self._codec_probe(vals, tables, nlive)
            return pkeys, jnp.logical_and(plive, exact)
        if mode == "raw":
            return vals[0], plive
        # packed: probe keys outside the packable range cannot equal any
        # (in-range) build key — mask them out instead of aliasing
        a, b = vals
        in_range = jnp.logical_and(
            jnp.abs(a) < (jnp.int64(1) << 31),
            jnp.logical_and(b >= 0, b < (jnp.int64(1) << 32) - 1),
        )
        keys = (a << 32) | (b & jnp.int64(0xFFFFFFFF))
        return keys, jnp.logical_and(plive, in_range)

    def _remaps_for(self, build_batch: ColumnBatch, pb: ColumnBatch):
        """Per key column: probe-code -> build-code remap array (or None
        when no dictionary translation is needed). Host-computed once per
        (key column, probe dictionary), exact via sorted-dict search."""
        out = []
        for bcol, pcol in self.on:
            bd = build_batch.column(bcol).dictionary
            # with a fused probe chain, pb is a RAW source batch: read
            # the key column under its pre-chain name (fusion guarantees
            # probe keys pass through the chain as plain references)
            pd_ = pb.column(self.probe_key_raw.get(pcol, pcol)).dictionary
            if bd is None and pd_ is None:
                out.append(None)
                continue
            if bd is None or pd_ is None:
                raise ExecutionError(
                    f"join key {bcol}={pcol} mixes utf8 and non-utf8 columns"
                )
            if bd is pd_:
                out.append(None)  # shared dictionary: codes comparable
                continue
            # cache holds BOTH dictionaries and is keyed per column
            # (identity-compared on hit): a GC'd dictionary whose address
            # is reused can't pick up a stale remap, a per-partition
            # build dictionary can't reuse another partition's remap, and
            # at most one pair per key column stays pinned
            cached = self._remap_cache.get(bcol)
            if cached is None or cached[0] is not bd or cached[1] is not pd_:
                from ..observability import trace_span
                from .. import columnar_registry

                with trace_span("host.dictionary", site="join.remap",
                                column=bcol, n_build=len(bd),
                                n_probe=len(pd_)):
                    # registry: same-entry pairs compose integer step
                    # remaps; cross-entry pairs build ONE cached sorted
                    # search per (content, content) pair process-wide
                    # (the legacy behavior rebuilt it per join instance
                    # per dictionary pair)
                    remap = columnar_registry.remap_between(pd_, bd)
                    if remap is None:  # identical coding: identity map
                        remap = np.arange(len(pd_), dtype=np.int64) \
                            if len(pd_) else np.full(1, -1, np.int64)
                    cached = (bd, pd_,
                              jnp.asarray(remap.astype(np.int64)))
                self._remap_cache[bcol] = cached
            out.append(cached[2])
        return tuple(out)

    def _probe_unique_batch(self, table, build_batch, pb: ColumnBatch,
                            mode: str, key_tables, remaps,
                            chained: bool = False) -> ColumnBatch:
        """Probe-aligned join of one batch as ONE program: the fused
        probe chain (unless ``chained``: ``_probe_inputs`` already ran
        it and compacted the batch, so the program is traced at the
        survivors' capacity), key extraction, the unique probe and
        assembly (a gather of every build column per probe ROW, dead or
        live — why a selective chain is compacted first)."""
        fn = self._unique_program(mode, chained)
        if table.dense_rows is None:
            _note_search(table, pb)
        return fn(table, build_batch, pb, key_tables, remaps)

    def _unique_program(self, mode: str, chained: bool):
        def build():
            tw = self.trace_twin()

            def run(table, bb: ColumnBatch, pb: ColumnBatch,
                    key_tables, remaps) -> ColumnBatch:
                if not chained:
                    pb = tw._probe_prologue(pb)
                pkeys, plive = tw._probe_keys(pb, mode, key_tables, remaps)
                build_rows, matched = join_k.probe_unique(table, pkeys, plive)
                return tw._assemble(bb, pb, build_rows, matched,
                                    pb.selection, None)

            return run

        return self.governed_jit(
            ("join.unique", mode) + _chained_key(chained), build)

    # general path: expanding probe -----------------------------------------
    #
    # Two programs around ONE host read of the match count. Before the
    # read, what costs a probe ROW (``join.ranges``: the chain, the keys,
    # two searches of the build keys, the running count); after it, what
    # costs an output SLOT (``join.expand``: each slot's probe and build
    # row, one gather an output column), traced at the rung of the count,
    # so a probe that keeps an eighth of its rows gathers an eighth.

    def _ranges_run(self, table, pb, mode, key_tables, remaps,
                    chained: bool):
        """One async ``join.ranges`` launch. Returns (the batch the fused
        chain left, or ``pb`` itself where none ran inside; lo; ends;
        total on device) WITHOUT syncing."""
        runs_chain = bool(self.probe_chain) and not chained

        def build():
            tw = self.trace_twin()

            def run(table, pb, key_tables, remaps):
                if runs_chain:
                    pb = tw._probe_prologue(pb)
                pkeys, plive = tw._probe_keys(pb, mode, key_tables,
                                              remaps)
                ranges = join_k.probe_ranges(table, pkeys, plive)
                # a batch the program did not change is not copied out
                return (pb if runs_chain else None,) + ranges

            return run

        fn = self.governed_jit(
            ("join.ranges", mode) + _chained_key(chained), build)
        _note_search(table, pb)
        kept, lo, ends, total = fn(table, pb, key_tables, remaps)
        return (pb if kept is None else kept), lo, ends, total

    def _expand_run(self, table, build_batch, kept: ColumnBatch, lo, ends,
                    total, t: int) -> ColumnBatch:
        """One ``join.expand`` launch for a probe batch whose ``t``
        matches are already counted: the packed prefix of ``t`` rows at
        the bucket ladder's rung for ``t``, never truncated and never
        re-run."""
        cap = bucket_capacity(t)

        def build():
            tw = self.trace_twin()

            def run(table, bb, pb, lo, ends, total):
                prows, brows, olive = join_k.expand_slots(
                    table, lo, ends, total, cap)
                return tw._assemble_expanded(bb, pb, prows, brows, olive)

            return run

        fn = self.governed_jit(("join.expand", cap), build)
        trace_event("join.expand", rows=t, probes=kept.capacity, to=cap,
                    cols=len(self.output_schema().fields))
        return fn(table, build_batch, kept, lo, ends, total)

    def _unmatched_batch(self, table, build_batch, pb, mode, key_tables,
                         remaps, chained: bool) -> ColumnBatch:
        """left/full: preserved probe rows with no match, null build
        columns. Pure device work — no sync."""
        def build():
            tw = self.trace_twin()

            def run_unmatched(table, bb, pb, key_tables, remaps):
                if not chained:
                    pb = tw._probe_prologue(pb)
                pkeys, plive = tw._probe_keys(pb, mode, key_tables,
                                              remaps)
                counts = join_k.probe_counts(table, pkeys)
                unmatched = jnp.logical_and(pb.selection,
                                            jnp.logical_or(
                                                jnp.logical_not(plive),
                                                counts == 0))
                zero = jnp.zeros((pb.capacity,), jnp.int32)
                no_match = jnp.zeros((pb.capacity,), jnp.bool_)
                return tw._assemble(bb, pb, zero, no_match, unmatched,
                                    None)

            return run_unmatched

        fn = self.governed_jit(
            ("join.unmatched", mode) + _chained_key(chained), build)
        return fn(table, build_batch, pb, key_tables, remaps)

    def _probe_expand_stream(self, table, build_batch, inputs,
                             mode: str, key_tables) -> Iterator[ColumnBatch]:
        """Expanding probe over a stream of ``_probe_inputs`` triples
        with DEFERRED count reads: ``join.ranges`` launches are
        asynchronous and the match totals of a whole window are fetched
        in ONE ``device_get`` (every blocking sync drains the device
        queue — q5's per-batch check was the dominant on-chip cost in
        the one chip record). Each batch is then expanded at the rung of
        its own count and yielded as it is: already the packed prefix, so
        nothing compacts it afterwards."""
        if self.how not in ("inner", "left", "full"):
            raise NotImplementedError_(
                f"{self.how} join with duplicate build keys"
            )
        # what a pending batch pins: the batch its chain left and two
        # int32 a probe row. Fixed-size-list columns hold ``length``
        # elements per row, so itemsize alone would under-count them
        row_bytes = 8 + sum(
            f.dtype.device_dtype().itemsize * (getattr(f.dtype, "length", 0)
                                               or 1)
            for f in self._probe_out_schema().fields)
        pend: list = []
        pend_bytes = 0

        def flush():
            nonlocal pend_bytes
            pend_bytes = 0
            if not pend:
                return
            from ..observability import trace_span

            with trace_span("device.block", site="join.expand_totals",
                            n=len(pend)):
                counts = jax.device_get([p[-1] for p in pend])  # ONE sync
            for (pb, remaps, chained, kept, lo, ends, total), t in zip(
                    pend, counts):
                yield self._expand_run(table, build_batch, kept, lo, ends,
                                       total, int(t))
                if self.how in ("left", "full"):
                    yield self._unmatched_batch(table, build_batch, pb,
                                                mode, key_tables, remaps,
                                                chained)
            pend.clear()

        for pb, remaps, chained in inputs:
            pend.append((pb, remaps, chained) + self._ranges_run(
                table, pb, mode, key_tables, remaps, chained))
            pend_bytes += pb.capacity * row_bytes
            if (len(pend) >= _SYNC_WINDOW
                    or pend_bytes >= _SYNC_WINDOW_BYTES):
                yield from flush()
        yield from flush()

    # assembly --------------------------------------------------------------

    def _assemble(self, bb, pb, build_rows, matched, probe_sel, _):
        """Probe-aligned output (no expansion). Traced."""
        schema = self.output_schema()
        if self.how == "semi":
            sel = jnp.logical_and(probe_sel, matched)
            return pb.with_selection(sel)
        if self.how == "anti":
            sel = jnp.logical_and(probe_sel, jnp.logical_not(matched))
            if self.null_aware:
                # NULL NOT IN (...) is unknown, not true: drop null keys
                for _, pcol in self.on:
                    v = pb.column(pcol).validity
                    if v is not None:
                        sel = jnp.logical_and(sel, v)
            return pb.with_selection(sel)
        if self.how == "inner":
            sel = jnp.logical_and(probe_sel, matched)
        else:  # left (probe-preserving outer)
            sel = probe_sel
        cols = []
        ps = pb.schema
        for f in schema.fields:
            if ps.has_field(f.name):
                c = pb.column(f.name)
                cols.append(c)
            else:
                c = bb.column(f.name)
                vals = jnp.take(c.values, build_rows)
                validity = jnp.take(c.validity, build_rows) if c.validity is not None \
                    else jnp.ones((pb.capacity,), jnp.bool_)
                validity = jnp.logical_and(validity, matched)
                cols.append(Column(vals, c.dtype, validity, c.dictionary))
        return ColumnBatch(schema, cols, sel, jnp.sum(sel).astype(jnp.int32))

    def _assemble_expanded(self, bb, pb, prows, brows, olive):
        schema = self.output_schema()
        cols = []
        ps = pb.schema
        for f in schema.fields:
            if ps.has_field(f.name):
                c = pb.column(f.name)
                vals = jnp.take(c.values, prows)
                validity = jnp.take(c.validity, prows) if c.validity is not None else None
            else:
                c = bb.column(f.name)
                vals = jnp.take(c.values, brows)
                validity = jnp.take(c.validity, brows) if c.validity is not None else None
            cols.append(Column(vals, c.dtype, validity, c.dictionary))
        return ColumnBatch(
            schema, cols, olive, jnp.sum(olive).astype(jnp.int32)
        )
