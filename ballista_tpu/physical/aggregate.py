"""Hash-aggregate physical operator (Partial / Final modes).

TPU-native equivalent of the reference's ``HashAggregateExec`` with its
Partial|Final mode enum (reference: rust/core/proto/ballista.proto:370-384;
two-phase split at rust/scheduler/src/planner.rs:149-171). Instead of a CPU
hash table, grouping is sort-based on device (kernels.aggregate); the whole
input pipeline + per-batch partial aggregation trace into one XLA program.

State layout: Partial emits "group columns + state columns" batches
(avg -> sum+count states), Final regroups the concatenated partial tables,
merges states, and finalizes (avg division in scaled int64 -> Decimal(6)).
Group capacity is adaptive: if a pass overflows, it re-runs with the next
power of two >= the true group count (one recompile, known exact).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar import Column, ColumnBatch, round_capacity
from ..datatypes import DataType, Decimal, Field, Float64, Int64, Schema
from ..errors import ExecutionError, NotImplementedError_
from .. import expr as ex
from ..kernels.aggregate import (
    AggInput,
    avg_fixed,
    dense_grouped_aggregate,
    dense_grouped_scatter,
    grouped_aggregate,
    scalar_aggregate,
)

# dictionary-coded group keys with product-of-cardinalities at or below
# this use the sort-free dense path
DENSE_GROUP_LIMIT = 256
from ..kernels.expr_eval import Evaluator
from ..observability.tracing import trace_event
from .base import (PhysicalPlan, Partitioning, concat_batches,
                   gather_batches, gathered_shape, shares_dictionaries)

DEFAULT_GROUP_CAPACITY = 1 << 12


def _state_ops(agg: ex.AggregateExpr):
    """[(state_suffix, op)] for one aggregate expr."""
    if agg.fn == "count":
        return [("count", "count")]
    if agg.fn == "sum":
        return [("sum", "sum")]
    if agg.fn == "avg":
        return [("sum", "sum"), ("count", "count")]
    if agg.fn in ("min", "max"):
        return [(agg.fn, agg.fn)]
    raise NotImplementedError_(f"aggregate fn {agg.fn}")


def _state_specs(agg: ex.AggregateExpr, idx: int, in_schema: Schema):
    """Partial mode: [(state_field_name, op, state_dtype)] typed from the
    original input schema."""
    if agg.fn == "count":
        return [(f"__s{idx}_count", "count", Int64)]
    dt = agg.expr.to_field(in_schema).dtype
    if agg.fn in ("sum", "avg"):
        if dt.is_integer:
            sum_t: DataType = Int64
        elif dt.kind == "decimal":
            sum_t = dt
        else:
            sum_t = Float64
        out = [(f"__s{idx}_sum", "sum", sum_t)]
        if agg.fn == "avg":
            out.append((f"__s{idx}_count", "count", Int64))
        return out
    return [(f"__s{idx}_{agg.fn}", agg.fn, dt)]


class HashAggregateExec(PhysicalPlan):
    """mode: 'partial' (per input partition) or 'final' (after merge)."""

    def __init__(
        self,
        mode: str,
        group_exprs: List[ex.Expr],
        agg_exprs: List[ex.Expr],  # AggregateExpr or Alias(AggregateExpr)
        child: PhysicalPlan,
        group_capacity: int = DEFAULT_GROUP_CAPACITY,
    ):
        assert mode in ("partial", "final")
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.agg_exprs = list(agg_exprs)
        self.child = child
        self.group_capacity = group_capacity
        self._in_schema = child.output_schema()
        self._ev = Evaluator(self._in_schema)
        self._aggs = [
            (e.name(), ex.strip_alias(e)) for e in self.agg_exprs
        ]
        for name, a in self._aggs:
            if not isinstance(a, ex.AggregateExpr):
                raise ExecutionError(f"not an aggregate expression: {name}")
        self._ranged_rejected = False
        # None = unprobed; () = permanently ineligible; else ONE tuple
        # (dict-length fingerprint, layout) — published atomically, so
        # concurrent partition execution (ingest iter_partitions) can
        # never pair one thread's layout with another's fingerprint
        self._mixed_cache = None

    # -- schemas ------------------------------------------------------------

    def group_fields(self) -> List[Field]:
        if self.mode == "partial":
            return [e.to_field(self._in_schema) for e in self.group_exprs]
        # final mode: group columns are already materialized in the input
        return [self._in_schema.field(e.name()) for e in self.group_exprs]

    def state_fields(self) -> List[Tuple[str, str, DataType]]:
        """Flattened (name, op, dtype) of all aggregate states."""
        out = []
        for i, (_, a) in enumerate(self._aggs):
            if self.mode == "partial":
                out.extend(_state_specs(a, i, self._in_schema))
            else:
                # final mode: dtype comes from the partial output schema
                for suffix, op in _state_ops(a):
                    name = f"__s{i}_{suffix}"
                    out.append((name, op, self._in_schema.field(name).dtype))
        return out

    def output_schema(self) -> Schema:
        gf = self.group_fields()
        if self.mode == "partial":
            sf = [Field(n, dt, True) for n, _, dt in self.state_fields()]
            return Schema(gf + sf)
        af = []
        for name, a in self._aggs:
            f = self._agg_output_field(name, a)
            af.append(f)
        return Schema(gf + af)

    def _agg_output_field(self, name: str, a: ex.AggregateExpr) -> Field:
        # final output dtype must match logical Aggregate schema; state
        # dtypes live in the partial schema under __s{i}_* names
        if a.fn == "count":
            return Field(name, Int64, False)
        i = self._agg_index(name)
        if a.fn == "avg":
            sum_f = self._in_schema.field(f"__s{i}_sum")
            if sum_f.dtype.kind == "decimal" or sum_f.dtype.is_integer:
                return Field(name, Decimal(6), True)
            return Field(name, Float64, True)
        if a.fn == "sum":
            return Field(name, self._in_schema.field(f"__s{i}_sum").dtype, True)
        return Field(name, self._in_schema.field(f"__s{i}_{a.fn}").dtype, True)

    def _agg_index(self, name: str) -> int:
        for i, (n, _) in enumerate(self._aggs):
            if n == name:
                return i
        raise ExecutionError(name)

    def output_partitioning(self) -> Partitioning:
        if self.mode == "partial":
            return self.child.output_partitioning()
        # final mode: one output partition per input partition (1 after a
        # merge; N when the partial states were hash-shuffled on the
        # group keys, in which case groups are co-located per partition)
        return Partitioning(
            "unknown", self.child.output_partitioning().num_partitions
        )

    def children(self):
        return [self.child]

    def with_new_children(self, children):
        return HashAggregateExec(
            self.mode, self.group_exprs, self.agg_exprs, children[0],
            self.group_capacity,
        )

    def display(self) -> str:
        g = ", ".join(e.name() for e in self.group_exprs)
        a = ", ".join(n for n, _ in self._aggs)
        return f"HashAggregateExec: mode={self.mode} gby=[{g}] aggr=[{a}]"

    def _signature_parts(self) -> tuple:
        from ..compile import fingerprint

        return (self.mode, fingerprint(self.group_exprs),
                fingerprint(self.agg_exprs), self._in_schema)

    # -- execution ----------------------------------------------------------

    def _device_prologue(self, batch: ColumnBatch) -> ColumnBatch:
        """Batch transform applied INSIDE every traced aggregation
        program, before key/input evaluation. Identity here;
        :class:`fusion.FusedStageExec` overrides it with the fused
        pipeline chain (scan→filter→project→partial-agg as ONE XLA
        program). Traced."""
        return batch

    def _program_input(self, inp) -> ColumnBatch:
        """First line of every traced aggregation program: the
        partition's batches laid end to end when it arrived as several
        (``_partition_input``), then the prologue. Traced: the copy, if
        XLA keeps one, is inside the program."""
        if not isinstance(inp, ColumnBatch):
            inp = gather_batches(inp)
        return self._device_prologue(inp)

    def _partition_input(self, schema: Schema, batches: List[ColumnBatch]):
        """``(inp, how)``: what the aggregation programs are handed for
        one partition, steered by what the batches show:

        - ``single``: one batch, as it is (donated when transient).
        - ``in_program``: several batches whose columns share their
          dictionary instances (a scan, from the table cache or a file),
          as a TUPLE: the program puts them together itself
          (``_program_input``), so no eager jax operation runs between
          the scan's last batch and the launch. jax's trace cache keys
          on the tuple's capacities: k rungs and a tail a scan. Never
          donated: the pieces may be pinned by the table cache.
        - ``host_concat``: dictionaries differ (shuffle partitions from
          independent producers): ``concat_batches`` unifies them on
          the host, and its fresh buffer is donated. The sort path
          (``_exec_grouped``) falls back to it too."""
        if len(batches) == 1:
            return batches[0], "single"
        if shares_dictionaries(batches):
            return tuple(batches), "in_program"
        return concat_batches(schema, batches), "host_concat"

    def _execute_over(self, schema: Schema, batches: List[ColumnBatch]
                      ) -> Iterator[ColumnBatch]:
        """One partition's batches through the aggregation programs, and
        one ``agg.inputs`` event that says ``how`` they reached them."""
        from ..cache.donation import mark_transient

        if not batches:
            return
        inp, how = self._partition_input(schema, batches)
        if not self.group_exprs:
            out = self._exec_scalar(inp)
        else:
            out, how = self._exec_grouped(inp, how)
        trace_event("agg.inputs", site=how, how=how, batches=len(batches),
                    capacity=sum(b.capacity for b in batches))
        # fresh program output, one downstream consumer: donatable
        mark_transient(out)
        yield out

    def execute(self, partition: int) -> Iterator[ColumnBatch]:
        yield from self._execute_over(
            self._in_schema, list(self.child.execute(partition)))

    # grouped ---------------------------------------------------------------

    def _agg_inputs_partial(self, batch: ColumnBatch) -> List[AggInput]:
        aggs: List[AggInput] = []
        for i, (_, a) in enumerate(self._aggs):
            specs = _state_specs(a, i, self._in_schema)
            for (_, op, dt) in specs:
                if op == "count":
                    if a.is_star or a.fn == "avg" and a.expr is None:
                        aggs.append(AggInput("count", None, None))
                    else:
                        r = self._ev.evaluate(a.expr, batch)
                        aggs.append(AggInput("count", None, r.validity))
                else:
                    r = self._ev.evaluate(a.expr, batch)
                    v = jnp.broadcast_to(r.values, (batch.capacity,))
                    v = self._to_state_dtype(v, r.dtype, dt)
                    aggs.append(AggInput(op, v, r.validity))
        return aggs

    def _agg_inputs_final(self, batch: ColumnBatch) -> List[AggInput]:
        aggs: List[AggInput] = []
        for name, op, dt in self.state_fields():
            col = batch.column(name)
            # merging states: counts and sums add up; min/min, max/max
            merge_op = "sum" if op in ("count", "sum") else op
            aggs.append(AggInput(merge_op, col.values, col.validity))
        return aggs

    def _to_state_dtype(self, v, src: DataType, dst: DataType):
        if dst.kind == "decimal" or dst.is_integer:
            return v.astype(jnp.int64)
        return v.astype(jnp.float32)

    def _run_grouping(self, batch: ColumnBatch, key_evals, aggs, cap):
        """Pick dense (sort-free) or sort-based grouping. Traced."""
        cards = []
        for r in key_evals:
            if r.dictionary is not None:
                cards.append(len(r.dictionary))
            elif r.dtype.kind == "boolean":
                cards.append(2)
            else:
                cards = None
                break
        if cards is not None:
            g_total = 1
            for r, card in zip(key_evals, cards):
                g_total *= card + (1 if r.validity is not None else 0)
            if 0 < g_total <= min(DENSE_GROUP_LIMIT, cap):
                gid = jnp.zeros((batch.capacity,), jnp.int32)
                for r, card in zip(key_evals, cards):
                    slots = card + (1 if r.validity is not None else 0)
                    code = jnp.broadcast_to(
                        r.values.astype(jnp.int32), (batch.capacity,)
                    )
                    if r.validity is not None:
                        # NULL keys take the extra slot per key column
                        code = jnp.where(r.validity, code, card)
                    gid = gid * slots + code
                return dense_grouped_aggregate(gid, batch.selection, aggs,
                                               g_total)
        keys = [
            jnp.broadcast_to(r.values, (batch.capacity,)) for r in key_evals
        ]
        key_validities = [r.validity for r in key_evals]
        return grouped_aggregate(keys, batch.selection, aggs, cap,
                                 key_validities)

    def _static_group_bound(self, batch: ColumnBatch) -> Optional[int]:
        """Host-side upper bound on the group count when every group key
        is a plain column with known cardinality (dictionary/boolean) —
        mirrors the dense-path condition in ``_run_grouping``. Lets
        ``_exec_grouped`` skip the overflow-check device sync entirely:
        a blocking device->host read costs a full round-trip when the
        accelerator is remote."""
        g = 1
        for e in self.group_exprs:
            if self.mode == "partial":
                base = ex.strip_alias(e)
                if not isinstance(base, ex.ColumnRef):
                    return None
                name = base.column
            else:
                name = e.name()
            try:
                col = batch.column(name)
            except Exception:  # noqa: BLE001 - unknown column: no bound
                return None
            if col.dictionary is not None:
                card = len(col.dictionary)
            elif col.dtype.kind == "boolean":
                card = 2
            else:
                return None
            g *= card + (1 if col.validity is not None else 0)
        return g if g > 0 else None

    # Ranged/mixed dense grouping: when every group key is either
    # dictionary-coded (static cardinality) or integer-valued with a
    # live range fitting below these bounds, rows aggregate by O(N)
    # scatter into a mixed-radix [G] table — no sort, no overflow retry.
    # The range cap bounds table memory; the live-rows factor keeps
    # pathological sparse keys (hash-like ids) on the sort path. 16x
    # measured neutral-or-better across TPC-H vs the original 4x (the
    # scatter table is cheap up to the absolute cap; q16's 3-key final
    # agg was falling to the sort path at 11x rows).
    _RANGED_DENSE_LIMIT = 1 << 23
    _RANGED_CAP_FACTOR = 16
    _RANGED_KINDS = ("int32", "int64", "decimal", "date32", "timestamp_ns")

    def _mixed_layout(self, batch: ColumnBatch):
        """Per group key: ("dict", slots) for dictionary/boolean keys or
        ("int", None) for integer-valued keys (incl. expressions, e.g.
        EXTRACT(YEAR ...)); None when any key is neither. Classified by
        TRACING the evaluator (jax.eval_shape — no compute). Kind
        classification is stable for the operator's lifetime, but dict
        SPANS are not: different partitions' batches carry different
        dictionaries, and a span cached from a smaller dictionary would
        overflow its mixed-radix digit and collide groups. The cache is
        therefore keyed on the batch's dictionary lengths and re-probed
        when they change."""
        cached = self._mixed_cache  # one read: (fp, layout) or ()/None
        if cached == ():  # dtype kinds never change: permanent
            return None
        fp = tuple(
            len(c.dictionary) if c.dictionary is not None else -1
            for c in batch.columns
        )
        if cached is not None and cached[0] == fp:
            return cached[1]
        meta: List = []

        def probe(b):
            b = self._device_prologue(b)
            kes, _ = self._inputs_and_keys(b)
            for r in kes:
                meta.append((r.dtype, r.dictionary))
            return [r.values for r in kes]

        try:
            jax.eval_shape(probe, batch)
        except Exception:  # noqa: BLE001 - untraceable: not eligible
            self._mixed_cache = ()
            return None
        layout = []
        for dt, d in meta:
            if d is not None:
                layout.append(("dict", len(d) + 1))  # +1 NULL/code-0 slot
            elif dt.kind == "boolean":
                layout.append(("dict", 3))
            elif dt.kind in self._RANGED_KINDS:
                layout.append(("int", None))
            else:
                self._mixed_cache = ()
                return None
        self._mixed_cache = (fp, layout)  # atomic pair publication
        return layout

    def _mixed_stats(self, inp, layout):
        """(per-int-key (min, max) list, nlive): one jitted program,
        scalars only across the link."""

        def build():
            tw = self.trace_twin()

            def stats(b):
                b = tw._program_input(b)
                kes, _ = tw._inputs_and_keys(b)
                maxi = jnp.iinfo(jnp.int64).max
                mm = []
                for (kind, _), r in zip(layout, kes):
                    if kind != "int":
                        continue
                    v = jnp.broadcast_to(r.values, (b.capacity,)) \
                        .astype(jnp.int64)
                    live = b.selection
                    if r.validity is not None:
                        live = jnp.logical_and(live, r.validity)
                    mm.append((jnp.min(jnp.where(live, v, maxi)),
                               jnp.max(jnp.where(live, v, -maxi))))
                return mm, jnp.sum(b.selection.astype(jnp.int32))

            return stats

        fn = self.governed_jit(("agg.mstats", tuple(layout)), build)
        from ..observability import trace_span

        # launch OUTSIDE the span: a cold call compiles synchronously
        # and the governor already attributes that to the compile lane —
        # only the blocking fetch is device-blocked time
        res = fn(inp)
        with trace_span("device.block", site="agg.mstats"):
            mm, nlive = jax.device_get(res)
        return [(int(lo), int(hi)) for lo, hi in mm], int(nlive)

    def _exec_grouped(self, inp, how: str):
        """``(out, how)``. ``inp`` is one batch or a tuple of them
        (``_partition_input``); the host-side choice of a path reads the
        shape of what the program will see, the dense and the ranged
        programs take ``inp`` itself. The SORT path concatenates a tuple
        on the host after all and says so in ``how``: its program holds
        a ``lax.sort``, which takes the chip's compiler 23 s at 16,384
        rows and minutes at a million, once a SHAPE, and the sums of
        rungs are a far smaller family than their tuples; beside a sort
        the eager copy is noise."""
        batch = (inp if isinstance(inp, ColumnBatch)
                 else gathered_shape(inp))
        cap = self.group_capacity
        bound = self._static_group_bound(batch)
        if bound is not None and bound <= min(DENSE_GROUP_LIMIT, cap):
            # one call, no overflow retry: safe to donate the batch
            out, _ng = self.governed_call(("agg.grouped", cap),
                                          self._grouped_build(cap), inp)
            return out, how  # dense path, can't overflow: no sync needed
        # rejected once (hash-like sparse ids / huge products) -> rejected
        # for the operator's lifetime: don't pay the stats round-trip again
        layout = None if self._ranged_rejected else self._mixed_layout(batch)
        if layout is not None:
            mm, nlive = self._mixed_stats(inp, layout)
            if any(lo > hi for lo, hi in mm):
                pass  # no live rows: sort path handles the empty batch
            else:
                spans, bases = [], []
                true_total = 1  # product of UNQUANTIZED spans
                it = iter(mm)
                for kind, slots in layout:
                    if kind == "dict":
                        spans.append(slots)
                        true_total *= slots
                    else:
                        lo, hi = next(it)
                        # +1 NULL slot; quantized so successive batches
                        # with similar ranges reuse one compiled program
                        spans.append(round_capacity(hi - lo + 2))
                        bases.append(lo)
                        true_total *= hi - lo + 2
                g_total = 1
                for s in spans:
                    g_total *= s
                # admission gates on LIVE rows (not capacity): sparse
                # post-filter batches must not allocate huge group tables.
                # The rows-proportional test uses the TRUE span product —
                # quantization (up to 2x per int key) is a compile-reuse
                # artifact, not a cost the data asked for; a 1.5M-group
                # final agg over a 6M-wide key must not lose the O(N)
                # path because 6M rounds to 8.4M (q18's HAVING subquery:
                # 3.7s sort -> 0.2s scatter). The quantized table still
                # has to fit the absolute limit.
                if (true_total <= self._RANGED_CAP_FACTOR * (nlive + 256)
                        and g_total <= self._RANGED_DENSE_LIMIT):
                    # final call on this batch (_mixed_stats's read has
                    # fully completed — device_get blocks): donatable
                    out, _ng = self.governed_call(
                        ("agg.mixed", tuple(spans), tuple(layout)),
                        self._mixed_build(tuple(spans), layout),
                        inp, jnp.asarray(bases, jnp.int64))
                    return out, how  # gid < G by construction: no sync
                self._ranged_rejected = True
        if not isinstance(inp, ColumnBatch):
            inp, how = concat_batches(batch.schema, list(inp)), "host_concat"
        # overflow-retry loop re-reads the SAME batch after an
        # undersized attempt — never donate here
        while True:
            fn = self._get_grouped_fn(cap, batch.capacity)
            out, num_groups = fn(inp)
            ng = int(num_groups)
            if ng <= cap:
                # persist the learned capacity: the operator instance is
                # reused across partitions AND collects (plan cache), so
                # later runs skip the undersized attempt + retry sync
                self.group_capacity = max(self.group_capacity, cap)
                return out, how
            cap = round_capacity(ng)

    def _inputs_and_keys(self, batch: ColumnBatch):
        """(key_evals, aggs) for the current mode. Traced."""
        if self.mode == "partial":
            key_evals = [self._ev.evaluate(e, batch) for e in self.group_exprs]
            aggs = self._agg_inputs_partial(batch)
        else:
            key_evals = [
                self._ev.evaluate(ex.ColumnRef(e.name()), batch)
                for e in self.group_exprs
            ]
            aggs = self._agg_inputs_final(batch)
        return key_evals, aggs

    def _assemble(self, batch: ColumnBatch, key_evals, res, cap: int):
        """GroupedResult -> output ColumnBatch. Traced."""
        out_cols: List[Column] = []
        gf = self.group_fields()
        for f, r in zip(gf, key_evals):
            vals = jnp.take(
                jnp.broadcast_to(r.values, (batch.capacity,)),
                res.rep_indices,
            )
            validity = (
                jnp.take(r.validity, res.rep_indices)
                if r.validity is not None
                else None
            )
            out_cols.append(Column(vals, f.dtype, validity, r.dictionary))
        if self.mode == "partial":
            for (name, op, dt), arr, va in zip(
                self.state_fields(), res.aggregates, res.agg_valid
            ):
                out_cols.append(Column(arr, dt, va, None))
        else:
            out_cols.extend(self._finalize(res))
        return ColumnBatch(
            self.output_schema(), out_cols, res.group_valid,
            jnp.minimum(res.num_groups, cap),
        )

    def _grouped_build(self, cap: int):
        def build():
            tw = self.trace_twin()  # don't pin the input subtree

            def run(inp):
                batch = tw._program_input(inp)
                key_evals, aggs = tw._inputs_and_keys(batch)
                res = tw._run_grouping(batch, key_evals, aggs, cap)
                return tw._assemble(batch, key_evals, res, cap), \
                    res.num_groups

            return run

        return build

    def _get_grouped_fn(self, cap: int, in_cap: int):
        # in_cap rides the traced batch shape; only the static group
        # capacity needs to be in the key
        return self.governed_jit(("agg.grouped", cap),
                                 self._grouped_build(cap))

    def _get_mixed_fn(self, spans, in_cap: int, layout):
        """Grouping program for mixed dict/ranged-int keys: mixed-radix
        gid over per-key slots (slot 0 of each radix = NULL), O(N)
        scatter aggregation, no sort and no overflow. Integer-key bases
        are a traced argument so consecutive batches with different
        ranges but the same quantized spans reuse one compiled
        program."""
        return self.governed_jit(("agg.mixed", spans, tuple(layout)),
                                 self._mixed_build(spans, layout))

    def _mixed_build(self, spans, layout):
        def build():
            tw = self.trace_twin()
            g_total = 1
            for s in spans:
                g_total *= s
            # pad the table so the output batch capacity is a power of
            # two (downstream jit caches key on capacity); gids stay
            # below the exact strides product
            G = round_capacity(g_total)

            def run(inp, bases):
                batch = tw._program_input(inp)
                key_evals, aggs = tw._inputs_and_keys(batch)
                gid = jnp.zeros((batch.capacity,), jnp.int64)
                bi = 0
                for (kind, _), span, r in zip(layout, spans, key_evals):
                    v = jnp.broadcast_to(r.values, (batch.capacity,))
                    if kind == "dict":
                        c = v.astype(jnp.int64) + 1
                    else:
                        c = v.astype(jnp.int64) - bases[bi] + 1
                        bi += 1
                    if r.validity is not None:
                        c = jnp.where(r.validity, c, 0)
                    gid = gid * span + c
                res = dense_grouped_scatter(gid.astype(jnp.int32),
                                            batch.selection, aggs, G)
                return tw._assemble(batch, key_evals, res, G), \
                    res.num_groups

            return run

        return build

    def _finalize(self, res) -> List[Column]:
        """final mode: merge states -> output aggregate columns."""
        cols: List[Column] = []
        state_arrays = res.aggregates
        si = 0
        for i, (name, a) in enumerate(self._aggs):
            ops = _state_ops(a)
            n_states = len(ops)
            arrs = state_arrays[si : si + n_states]
            dts = [
                self._in_schema.field(f"__s{i}_{suffix}").dtype
                for suffix, _ in ops
            ]
            si += n_states
            valids = res.agg_valid[si - n_states : si]
            out_f = self._agg_output_field(name, a)
            if a.fn == "count":
                cols.append(Column(arrs[0], Int64, None, None))
            elif a.fn == "avg":
                s, c = arrs[0], arrs[1]
                sum_dt = dts[0]
                if sum_dt.kind == "decimal" or sum_dt.is_integer:
                    scale = sum_dt.scale if sum_dt.kind == "decimal" else 0
                    val = avg_fixed(s, c, scale)
                    cols.append(Column(val, Decimal(6), c > 0, None))
                else:
                    val = s.astype(jnp.float32) / jnp.maximum(c, 1).astype(jnp.float32)
                    cols.append(Column(val, Float64, c > 0, None))
            else:  # sum/min/max: NULL when no valid input was seen
                cols.append(Column(arrs[0], out_f.dtype, valids[0], None))
        return cols

    # ungrouped -------------------------------------------------------------

    def _scalar_build(self):
        def build():
            tw = self.trace_twin()

            def run(inp):
                b = tw._program_input(inp)
                if tw.mode == "partial":
                    aggs = tw._agg_inputs_partial(b)
                else:
                    aggs = tw._agg_inputs_final(b)
                return scalar_aggregate(b.selection, aggs)

            return run

        return build

    def _get_scalar_fn(self):
        return self.governed_jit(("agg.scalar",), self._scalar_build())

    def _exec_scalar(self, inp) -> ColumnBatch:
        # single call, input never touched again: donate when transient
        vals, valids = self.governed_call(("agg.scalar",),
                                          self._scalar_build(), inp)

        cap = 8
        sel = np.zeros(cap, dtype=bool)
        sel[0] = True

        def expand(v, valid, dt):
            arr = jnp.zeros((cap,), dt.device_dtype()).at[0].set(
                v.astype(dt.device_dtype())
            )
            validity = (
                jnp.zeros((cap,), jnp.bool_).at[0].set(valid)
                if valid is not None
                else None
            )
            return arr, validity

        cols: List[Column] = []
        if self.mode == "partial":
            schema = self.output_schema()
            for (name, op, dt), v, va in zip(self.state_fields(), vals, valids):
                arr, validity = expand(v, va, dt)
                cols.append(Column(arr, dt, validity, None))
        else:
            schema = self.output_schema()
            si = 0
            for i, (name, a) in enumerate(self._aggs):
                ops = _state_ops(a)
                arrs = vals[si : si + len(ops)]
                vas = valids[si : si + len(ops)]
                dts = [
                    self._in_schema.field(f"__s{i}_{suffix}").dtype
                    for suffix, _ in ops
                ]
                si += len(ops)
                out_f = self._agg_output_field(name, a)
                if a.fn == "avg":
                    s, c = arrs[0], arrs[1]
                    sum_dt = dts[0]
                    if sum_dt.kind == "decimal" or sum_dt.is_integer:
                        scale = sum_dt.scale if sum_dt.kind == "decimal" else 0
                        v = avg_fixed(s, c, scale)
                    else:
                        v = s.astype(jnp.float32) / jnp.maximum(c, 1).astype(
                            jnp.float32
                        )
                    arr, validity = expand(v, c > 0, out_f.dtype)
                    cols.append(Column(arr, out_f.dtype, validity, None))
                elif a.fn == "count":
                    arr, _ = expand(arrs[0], None, out_f.dtype)
                    cols.append(Column(arr, out_f.dtype, None, None))
                else:  # sum/min/max: NULL when no valid input
                    arr, validity = expand(arrs[0], vas[0], out_f.dtype)
                    cols.append(Column(arr, out_f.dtype, validity, None))
        return ColumnBatch(
            schema, cols, jnp.asarray(sel), jnp.asarray(np.int32(1))
        )
