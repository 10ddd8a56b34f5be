"""Pipelined parallel ingest: overlap parse, H2D and compute.

The scan path used to be a fully serial pull loop — the device idled
while the CPU parsed, and the CPU idled while the device computed
(ROADMAP: post-compile-governor cold mass is parse+H2D+execute, ~14s
for q5's 8-table register+scan at SF1). The reference engine reads
partitions concurrently on tokio workers; this package is the
TPU-native equivalent, with three overlap axes:

- **cross-table** — :func:`prime_plan` starts every leaf scan's
  parse+H2D on a shared bounded thread pool
  (``BALLISTA_INGEST_THREADS``) before any consumer pulls, so
  independent tables (q5 joins eight) parse concurrently;
- **intra-query** — each scan streams through a bounded
  :class:`PrefetchHandle` queue (``BALLISTA_PREFETCH_BATCHES``,
  double-buffered by default): chunk N+1 parses on CPU while chunk N
  transfers/computes on device, with H2D issued from the producer
  thread (``ColumnBatch.from_numpy`` uploads as it builds);
- **cluster** — ``ShuffleReaderExec`` fetches a group's partition
  files concurrently and prefetches the next group behind the
  consumer (:func:`parallel_map` / the reader's in-flight futures).

Default ON; ``BALLISTA_INGEST_THREADS=1`` plus
``BALLISTA_PREFETCH_BATCHES=0`` restore the serial pull loop exactly.
Results are byte-identical either way — the pipeline reorders *timing*,
never rows (pinned by tests/test_ingest.py's determinism sweep).

Observability: the io layer brackets its work in :func:`phases.phase`
timers, which land on the owning scan's ``MetricsSet`` as
``elapsed_parse``/``elapsed_h2d`` (EXPLAIN ANALYZE renders them), emit
``ingest.parse``/``ingest.h2d`` spans under ``BALLISTA_TRACE=1`` (the
producer-thread tids make the overlap visible), and accumulate into
process totals ``phase_totals()`` that callers join with wall time
for the parse/H2D/execute cold-path attribution.
"""

from .config import (  # noqa: F401
    ingest_threads,
    prefetch_batches,
    reconfigure,
)
from .phases import (  # noqa: F401
    PhaseRecorder,
    bound_iter,
    phase,
    phase_bytes,
    phase_totals,
    reset_phase_totals,
)
from .pipeline import (  # noqa: F401
    KeyedLocks,
    PrefetchHandle,
    cancel_plan,
    ingest_pool,
    iter_partitions,
    parallel_map,
    pool_queue_depth,
    prime_plan,
)
