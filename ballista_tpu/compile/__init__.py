"""Compile governor: kernel compilation as a managed, observable resource.

Two parts (see docs/compile_cache.md):

- :mod:`buckets`  — shape canonicalization: batch capacities quantize
  onto a geometric row-count ladder (``BALLISTA_SHAPE_BUCKETS*`` knobs)
  so uneven partitions hit a handful of compiled signatures;
- :mod:`governor` — the single process-wide jit cache replacing the
  per-instance/module ad-hoc dicts (adaptive re-plans now reuse every
  trace), with compile counts/seconds/cache hits flowing into operator
  metrics, EXPLAIN ANALYZE and ``BALLISTA_TRACE`` spans.

``dev/check_jit_sites.py`` (tier-1-run lint) keeps ``jax.jit`` call
sites from regrowing outside this package.
"""

from .buckets import (  # noqa: F401
    bucket_capacity,
    bucket_ladder,
    buckets_enabled,
    reconfigure,
)
from .governor import (  # noqa: F401
    MESH_NS_CAP,
    CompileGovernor,
    GovernedFunction,
    compile_stats,
    governed,
    governor,
    reset_compile_stats,
)
from .keys import fingerprint  # noqa: F401
