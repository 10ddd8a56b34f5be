"""Optional prewarm pass: start compiling while the scan parses.

The cold path serializes parse -> host-to-device upload -> first compile
(parse and H2D sit entirely before the first XLA compile). With the bucket ladder, the capacity a scan will emit is
predictable from its estimated row count BEFORE any byte is parsed — so
a background thread can AOT-compile the scan-side fused pipeline chains
at the predicted rung concurrently with parse/H2D.

Best-effort by design: utf8 columns get placeholder dictionaries, so a
chain whose trace bakes dictionary content (string-literal comparisons,
hash repartitioning) lowers to different HLO and the prewarm compile is
wasted — but never wrong, because the real call re-traces through the
same governed entry. Chains over numeric/date predicates (the common
TPC-H shape) produce identical HLO, and the persistent compilation cache
turns the real call's compile into a fast disk hit even though the
in-memory trace cache misses on the placeholder treedef.

Gated by ``BALLISTA_PREWARM`` (default off — an extra thread compiling
speculatively is the wrong default for test suites and tiny queries).
"""

from __future__ import annotations

import os
import threading
from typing import List, Optional, Tuple

from .buckets import bucket_capacity


def prewarm_enabled() -> bool:
    return os.environ.get("BALLISTA_PREWARM", "").lower() in (
        "1", "on", "true")


def abstract_batch(schema, cap: int):
    """ColumnBatch pytree of ``jax.ShapeDtypeStruct`` leaves — enough
    for ``jit.lower`` without any real data. utf8 columns carry empty
    placeholder dictionaries; no validity (scans attach validity only
    when the file actually has NULLs)."""
    import jax
    import numpy as np

    from ..columnar import Column, ColumnBatch, Dictionary

    cols = []
    for f in schema.fields:
        dt = f.dtype.device_dtype()
        shape = (cap, f.dtype.length) if f.dtype.kind == "list" else (cap,)
        cols.append(Column(
            jax.ShapeDtypeStruct(shape, dt), f.dtype, None,
            Dictionary([]) if f.dtype.kind == "utf8" else None,
        ))
    return ColumnBatch(
        schema, cols,
        jax.ShapeDtypeStruct((cap,), np.bool_),
        jax.ShapeDtypeStruct((), np.int32),
    )


def _scan_estimate(source) -> "Optional[Tuple[int, int]]":
    """(predicted per-partition emit capacity, estimated per-partition
    rows) of a table source, or None when it cannot be estimated —
    estimation may probe file metadata, so callers needing both figures
    share one call. Mirrors the quantization the sources apply at emit
    time (io/text.py / io/parquet.py)."""
    est = None
    try:
        est = source.estimated_rows()
    except Exception:  # noqa: BLE001 - estimation is best-effort
        return None
    if not est:
        return None
    nparts = max(source.num_partitions(), 1)
    per_part = max(est // nparts, 1)
    cap = bucket_capacity(per_part)
    # unwrap caching decorators: the emit cap lives on the inner scanner
    inner = source
    while not hasattr(inner, "_capacity") and hasattr(inner, "inner"):
        inner = inner.inner
    limit = getattr(inner, "_capacity", None)
    if isinstance(limit, int) and limit > 0:
        cap = min(cap, limit)
    return cap, per_part


def _scan_capacity_hint(source) -> Optional[int]:
    hint = _scan_estimate(source)
    return hint[0] if hint is not None else None


def _fused_capacity_hint(source) -> Optional[int]:
    """Predicted capacity of a fused stage's CONCATENATED scan input.
    A chunked scan emits full chunks at the scanner's capacity limit
    plus one remainder rung; the fused stage concats them (exact sum —
    see base.concat_batches). Best-effort like everything here."""
    hint = _scan_estimate(source)
    if hint is None:
        return None
    per_part, rows = hint
    if rows <= per_part:
        return per_part
    chunks, rem = divmod(rows, per_part)
    return chunks * per_part + (bucket_capacity(rem) if rem else 0)


def collect_targets(phys) -> List[Tuple[object, object]]:
    """(governed fn, abstract input batch) for every program whose
    first compile currently waits for parse + H2D to finish: fused
    aggregate stages rooted on a table scan (the whole-stage-fusion
    shape — prewarm and fusion share one key space), plus any bare
    pipeline chain still rooted on a scan (e.g. join build sides)."""
    from ..physical.base import PipelineOp
    from ..physical.fusion import FusedDistinctCountExec, FusedStageExec
    from ..physical.operators import ScanExec

    targets: List[Tuple[object, object]] = []
    seen = set()

    def scan_batch(source: ScanExec, fused: bool):
        cap = (_fused_capacity_hint(source.source) if fused
               else _scan_capacity_hint(source.source))
        if cap is None:
            return None
        try:
            return abstract_batch(source.output_schema(), cap)
        except Exception:  # noqa: BLE001 - exotic schema
            return None

    def walk(node, parent_is_pipeline: bool) -> None:
        if isinstance(node, (FusedStageExec, FusedDistinctCountExec)) \
                and id(node) not in seen:
            seen.add(id(node))
            if isinstance(node.source, ScanExec):
                batch = scan_batch(node.source, fused=True)
                if batch is not None:
                    if isinstance(node, FusedDistinctCountExec):
                        fn = node._get_fn(node.group_capacity)
                    elif node.group_exprs:
                        fn = node._get_grouped_fn(node.group_capacity,
                                                  batch.capacity)
                    else:
                        fn = node._get_scalar_fn()
                    targets.append((fn, batch))
            for c in node.children():
                walk(c, False)
            return
        is_pipe = isinstance(node, PipelineOp)
        if is_pipe and not parent_is_pipeline and id(node) not in seen:
            seen.add(id(node))
            chain, source = node._pipeline_chain()
            if isinstance(source, ScanExec):
                batch = scan_batch(source, fused=False)
                if batch is not None:
                    targets.append((node._fused_governed(), batch))
        for c in node.children():
            walk(c, is_pipe)

    walk(phys, False)
    return targets


def maybe_prewarm(phys) -> Optional[threading.Thread]:
    """Kick off background compilation of ``phys``'s scan-side pipeline
    chains (once per plan instance). Returns the thread, or None when
    disabled / nothing to warm. Fire-and-forget: compilation is pure, a
    racing foreground compile of the same program is just wasted work,
    never wrong."""
    if not prewarm_enabled() or getattr(phys, "_prewarmed", False):
        return None
    try:
        phys._prewarmed = True
    except AttributeError:  # exotic root without a __dict__
        return None
    try:
        targets = collect_targets(phys)
    except Exception:  # noqa: BLE001 - prewarm must never break a query
        return None
    if not targets:
        return None

    def run() -> None:
        for fn, batch in targets:
            fn.warm(batch)

    t = threading.Thread(target=run, name="ballista-prewarm", daemon=True)
    t.start()
    return t
