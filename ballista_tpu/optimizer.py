"""Logical plan optimizer.

The reference delegates optimization to DataFusion's optimizer before
distributed planning (reference: rust/scheduler/src/lib.rs:317-331 calls
``ctx.optimize``); for a TPU engine the two rules that matter most are
implemented natively:

- **filter pushdown**: WHERE conjuncts sink below joins to the side whose
  columns they reference (cuts probe/build sizes before any device work);
- **projection pruning**: table scans read only referenced columns (string
  columns that are never touched skip dictionary building entirely).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Set

from . import expr as ex
from .errors import PlanError
from .logical import (
    Aggregate,
    EmptyRelation,
    Explain,
    Filter,
    Join,
    Limit,
    LogicalPlan,
    Projection,
    Repartition,
    Sort,
    TableScan,
)


def optimize(plan: LogicalPlan) -> LogicalPlan:
    plan = push_filters(plan)
    plan = push_semi_joins(plan)
    plan = prune_columns(plan, None)
    return plan


# ---------------------------------------------------------------------------
# Semi/anti-join pushdown
# ---------------------------------------------------------------------------


def _map_children(plan: LogicalPlan, fn) -> LogicalPlan:
    """Rebuild ``plan`` with ``fn`` applied to every LogicalPlan field."""
    updates = {
        f.name: fn(v)
        for f in dataclasses.fields(plan)
        if isinstance(v := getattr(plan, f.name), LogicalPlan)
    }
    return dataclasses.replace(plan, **updates) if updates else plan


def _may_prune(plan: LogicalPlan) -> bool:
    """True when the subtree can shrink cardinality beyond FK matching
    (filters, limits, aggregates, semi/anti joins)."""
    if isinstance(plan, (Filter, Limit, Aggregate)):
        return True
    if isinstance(plan, Join) and plan.how in ("semi", "anti"):
        return True
    return any(_may_prune(c) for c in plan.children())


def push_semi_joins(plan: LogicalPlan) -> LogicalPlan:
    """Sink a semi/anti join below an inner join toward the input that
    produces its key columns.

    ``(A ⋈ B) ⋉ S`` on a key from A rewrites to ``(A ⋉ S) ⋈ B``: the
    key column rides through the inner join unchanged, so membership
    against S filters the same rows — but now BEFORE the join, so the
    join (and everything above it) runs at the pruned size. TPC-H q18's
    IN-subquery semi drops from probing the full 3-table join output to
    pruning orders at the scan (6M-row join shapes -> tens of rows).

    Guard: only applied when the OTHER inner-join input cannot itself
    prune (no filters/limits/aggregates/semi-antis beneath it). When it
    can — q21's exists/not-exists over a heavily filtered join — the
    child join may shrink the key side far below the pre-join table,
    and hoisted (current) placement probes fewer rows. Runs after
    push_filters so filters sit at their final depth.

    The reference gets this class of transform from DataFusion's
    decorrelation/filter-pushdown stack (reference: rust/scheduler/src/
    lib.rs:317-331 delegates to ctx.optimize); here it is native."""
    plan = _map_children(plan, push_semi_joins)
    if not (isinstance(plan, Join) and plan.how in ("semi", "anti")):
        return plan
    child = plan.left
    if not (isinstance(child, Join) and child.how == "inner"):
        return plan
    keys = [l for l, _ in plan.on]
    lnames = set(child.left.schema().names())
    rnames = set(child.right.schema().names())
    # name collisions resolve to the inner join's LEFT output column
    if all(k in lnames for k in keys) and not _may_prune(child.right):
        pushed = Join(child.left, plan.right, plan.on, plan.how,
                      plan.null_aware)
        return dataclasses.replace(child, left=push_semi_joins(pushed))
    if (all(k in rnames and k not in lnames for k in keys)
            and not _may_prune(child.left)):
        pushed = Join(child.right, plan.right, plan.on, plan.how,
                      plan.null_aware)
        return dataclasses.replace(child, right=push_semi_joins(pushed))
    return plan


# ---------------------------------------------------------------------------
# Filter pushdown
# ---------------------------------------------------------------------------


def split_conjuncts(e: ex.Expr) -> List[ex.Expr]:
    if isinstance(e, ex.BinaryExpr) and e.op == "and":
        return split_conjuncts(e.left) + split_conjuncts(e.right)
    return [e]


def conjoin(parts: List[ex.Expr]) -> ex.Expr:
    out = parts[0]
    for p in parts[1:]:
        out = ex.BinaryExpr(out, "and", p)
    return out


def split_disjuncts(e: ex.Expr) -> List[ex.Expr]:
    if isinstance(e, ex.BinaryExpr) and e.op == "or":
        return split_disjuncts(e.left) + split_disjuncts(e.right)
    return [e]


def _structural_key(e: ex.Expr) -> str:
    """Structural identity INCLUDING table qualifiers (display name() drops
    them, which would wrongly equate n1.n_name with n2.n_name)."""
    if isinstance(e, ex.ColumnRef):
        return f"col:{e.qualified()}"
    parts = [type(e).__name__]
    for attr in ("op", "alias_name", "pattern", "negated", "fn", "value",
                 "dtype", "ascending", "is_star"):
        if hasattr(e, attr):
            parts.append(repr(getattr(e, attr)))
    for c in e.children():
        parts.append(_structural_key(c))
    return "(" + " ".join(parts) + ")"


def factor_or(e: ex.Expr) -> List[ex.Expr]:
    """(A and X) or (A and Y) -> [A, (X or Y)] — plus derived IN lists.

    Pulls conjuncts common to every OR branch to the top (matched by
    qualifier-aware structural key). TPC-H q19's OR-of-ANDs hides its join
    condition this way; factoring exposes it to the join-graph extractor.

    Additionally derives IMPLIED per-column predicates: when every branch
    pins the same column to literal(s) (``c = v`` / ``c IN (...)``), the
    OR implies ``c IN (union)`` — a redundant-but-pushable conjunct. q7's
    ``(n1=F AND n2=G) OR (n1=G AND n2=F)`` shares no common conjunct, yet
    implies n1 IN (F,G) AND n2 IN (F,G), which pushdown sinks onto the
    nation scans so the join pyramid above them shrinks by ~12x.
    """
    branches = split_disjuncts(e)
    if len(branches) < 2:
        return [e]
    branch_sets = [
        {_structural_key(c): c for c in split_conjuncts(b)} for b in branches
    ]
    common_names = set(branch_sets[0])
    for s in branch_sets[1:]:
        common_names &= set(s)
    if not common_names:
        return [e] + _derive_in_predicates(branches)
    out: List[ex.Expr] = [branch_sets[0][n] for n in sorted(common_names)]
    residuals = []
    for s in branch_sets:
        rest = [c for n, c in s.items() if n not in common_names]
        if not rest:
            # a branch with no residual makes the OR vacuous beyond the
            # common part
            return out
        residuals.append(conjoin(rest))
    ored = residuals[0]
    for r in residuals[1:]:
        ored = ex.BinaryExpr(ored, "or", r)
    out.append(ored)
    # derive from the residuals only: the factored commons already pin
    # their columns exactly
    return out + _derive_in_predicates(residuals)


def _branch_literal_constraints(branch: ex.Expr):
    """column structural key -> (ColumnRef, literal values) for conjuncts
    of the form ``col = lit`` / ``col IN (lits)``. None values = column
    not literal-pinned in this branch."""
    out = {}
    for c in split_conjuncts(branch):
        col = vals = None
        if isinstance(c, ex.BinaryExpr) and c.op == "=":
            if isinstance(c.left, ex.ColumnRef) and isinstance(
                    c.right, ex.Literal):
                col, vals = c.left, [c.right]
            elif isinstance(c.right, ex.ColumnRef) and isinstance(
                    c.left, ex.Literal):
                col, vals = c.right, [c.left]
        elif (isinstance(c, ex.InList) and not c.negated
              and isinstance(c.expr, ex.ColumnRef)
              and all(isinstance(v, ex.Literal) for v in c.list)):
            col, vals = c.expr, list(c.list)
        if col is not None:
            key = _structural_key(col)
            entry = out.setdefault(key, (col, []))
            entry[1].extend(vals)
    return out


def _derive_in_predicates(branches) -> List[ex.Expr]:
    """Columns literal-pinned in EVERY branch -> implied IN conjuncts."""
    maps = [_branch_literal_constraints(b) for b in branches]
    keys = set(maps[0])
    for m in maps[1:]:
        keys &= set(m)
    derived = []
    for k in sorted(keys):
        col = maps[0][k][0]
        seen, lits = set(), []
        for m in maps:
            for lit in m[k][1]:
                if lit.value not in seen:
                    seen.add(lit.value)
                    lits.append(lit)
        derived.append(ex.InList(col, lits))
    return derived


def push_filters(plan: LogicalPlan) -> LogicalPlan:
    if isinstance(plan, Filter):
        child = push_filters(plan.input)
        conjuncts = split_conjuncts(plan.predicate)
        return _sink(conjuncts, child)
    if isinstance(plan, Projection):
        return Projection(plan.exprs, push_filters(plan.input))
    if isinstance(plan, Aggregate):
        return Aggregate(plan.group_exprs, plan.agg_exprs, push_filters(plan.input))
    if isinstance(plan, Sort):
        return Sort(plan.sort_exprs, push_filters(plan.input))
    if isinstance(plan, Limit):
        return Limit(plan.n, push_filters(plan.input))
    if isinstance(plan, Repartition):
        return Repartition(push_filters(plan.input), plan.num_partitions,
                           plan.hash_exprs)
    if isinstance(plan, Join):
        # dataclasses.replace: never silently drop a Join field
        return dataclasses.replace(plan, left=push_filters(plan.left),
                                   right=push_filters(plan.right))
    if isinstance(plan, Explain):
        return Explain(push_filters(plan.input), plan.verbose, plan.analyze)
    return plan


def _sink(conjuncts: List[ex.Expr], node: LogicalPlan) -> LogicalPlan:
    """Place each conjunct as low as possible over ``node``."""
    if isinstance(node, Join) and node.how == "inner":
        lcols = set(node.left.schema().names())
        rcols = set(node.right.schema().names())
        left_preds, right_preds, keep = [], [], []
        for c in conjuncts:
            refs = set(ex.referenced_columns(c))
            if refs and refs <= lcols:
                left_preds.append(c)
            elif refs and refs <= rcols:
                right_preds.append(c)
            else:
                keep.append(c)
        left = _sink(left_preds, node.left) if left_preds else node.left
        right = _sink(right_preds, node.right) if right_preds else node.right
        out: LogicalPlan = dataclasses.replace(node, left=left, right=right)
        if keep:
            out = Filter(conjoin(keep), out)
        return out
    if isinstance(node, Filter):
        # merge adjacent filters, keep sinking
        return _sink(conjuncts + split_conjuncts(node.predicate), node.input)
    if not conjuncts:
        return node
    return Filter(conjoin(conjuncts), node)


# ---------------------------------------------------------------------------
# Projection pruning
# ---------------------------------------------------------------------------


def _cols_of(exprs) -> Set[str]:
    out: Set[str] = set()
    for e in exprs:
        out.update(ex.referenced_columns(e))
    return out


def _join_columns(plan: Join, required: Optional[Set[str]], left: LogicalPlan):
    """What a join emits of its schema when its parent reads only
    ``required``: the join keys and a pushed-down filter's columns stay
    in its INPUTS and stop there. Semi/anti joins emit their probe side
    under a selection and gather nothing, so they carry no list.
    ``left`` is the left input as pruned."""
    if required is None or plan.how in ("semi", "anti"):
        return plan.columns
    names = plan.schema().names()
    keep = tuple(n for n in names if n in required)
    if not keep:  # count(*)-style parent: one column carries the rows,
        # and it has to be one the pruned inputs still emit
        keep = (left.schema().names()[0],)
    return plan.columns if len(keep) == len(names) else keep


def prune_columns(plan: LogicalPlan, required: Optional[Set[str]]) -> LogicalPlan:
    """required=None means every column of this node's schema is needed."""
    if isinstance(plan, TableScan):
        if required is None:
            return plan
        schema = plan.source.table_schema()
        names = [n for n in schema.names() if n in required]
        if not names:  # degenerate count(*)-style scan: keep first column
            names = [schema.names()[0]]
        return TableScan(plan.table_name, plan.source, tuple(names))
    if isinstance(plan, Projection):
        need = _cols_of(plan.exprs)
        return Projection(plan.exprs, prune_columns(plan.input, need))
    if isinstance(plan, Filter):
        need = None if required is None else set(required) | _cols_of([plan.predicate])
        return Filter(plan.predicate, prune_columns(plan.input, need))
    if isinstance(plan, Aggregate):
        need = _cols_of(plan.group_exprs) | _cols_of(plan.agg_exprs)
        return Aggregate(plan.group_exprs, plan.agg_exprs,
                         prune_columns(plan.input, need))
    if isinstance(plan, Sort):
        need = None if required is None else set(required) | _cols_of(plan.sort_exprs)
        return Sort(plan.sort_exprs, prune_columns(plan.input, need))
    if isinstance(plan, Limit):
        return Limit(plan.n, prune_columns(plan.input, required))
    if isinstance(plan, Repartition):
        need = required
        if plan.hash_exprs and required is not None:
            need = set(required) | _cols_of(plan.hash_exprs)
        return Repartition(prune_columns(plan.input, need),
                           plan.num_partitions, plan.hash_exprs)
    if isinstance(plan, Join):
        lnames = set(plan.left.schema().names())
        rnames = set(plan.right.schema().names())
        on_l = {l for l, _ in plan.on}
        on_r = {r for _, r in plan.on}
        if required is None:
            lneed, rneed = None, None
        else:
            lneed = (set(required) & lnames) | on_l
            rneed = (set(required) & rnames) | on_r
        left = prune_columns(plan.left, lneed)
        return dataclasses.replace(plan, left=left,
                                   right=prune_columns(plan.right, rneed),
                                   columns=_join_columns(plan, required, left))
    if isinstance(plan, Explain):
        return Explain(prune_columns(plan.input, None), plan.verbose,
                       plan.analyze)
    return plan
