"""ballista-tpu: a TPU-native distributed SQL/DataFrame query engine.

A from-scratch re-design of the capabilities of the reference engine
(Ballista, a Rust/Arrow distributed query engine "largely inspired by Apache
Spark" — reference: README.md:57-70, docs/architecture.md:5-46) for TPU
hardware: query stages compile to single XLA programs over columnar device
buffers, shuffles ride ICI ``all_to_all`` inside a slice and a host data
plane across slices, and the scheduler/executor control plane speaks gRPC.

Layering (mirrors reference SURVEY layer map, bottom-up):
  columnar/datatypes  - fixed-capacity struct-of-arrays batches (L0/L1)
  expr/logical/sql    - expression AST, logical plan, SQL frontend (L1/L5)
  physical/kernels    - XLA operator kernels + physical plans (L1)
  proto/serde         - wire contract (L2)
  distributed         - scheduler, executor, state, shuffle (L3/L4)
  client              - BallistaContext / DataFrame API (L5/L6)
"""

import os as _os
import sys as _sys

import jax as _jax

# pyarrow >= 25 defaults its memory pool to mimalloc, which intermittently
# corrupts under this engine's thread mix (executor thread pools + grpc +
# GIL-released ctypes scans): observed as flaky SIGSEGV inside pa.array
# during shuffle writes, reproducibly gone under jemalloc or the system
# allocator. Pin jemalloc BEFORE pyarrow's first import (the env var is
# only read then); if the application imported pyarrow already, flip the
# default pool at runtime instead. An explicit ARROW_DEFAULT_MEMORY_POOL
# from the user always wins.
if "ARROW_DEFAULT_MEMORY_POOL" not in _os.environ:
    if "pyarrow" in _sys.modules:
        try:
            import pyarrow as _pa

            _pa.set_memory_pool(_pa.jemalloc_memory_pool())
        except Exception:  # noqa: BLE001 - jemalloc absent in this build
            pass
    else:
        _os.environ["ARROW_DEFAULT_MEMORY_POOL"] = "jemalloc"
        # mark the choice as OURS by recording the VALUE we set:
        # io/ipc.py's runtime fallback must not override a pool the USER
        # explicitly selected, and child processes inherit this marker —
        # so it only counts as ours while ARROW_DEFAULT_MEMORY_POOL still
        # equals what we wrote (a user override in the child wins)
        _os.environ["_BALLISTA_SET_ARROW_POOL"] = "jemalloc"

# Exact decimal arithmetic uses scaled int64 columns; without x64, JAX would
# silently downcast them to int32. Float64 device arrays are never created
# (the engine stores logical f64 as f32 on device; see datatypes.py).
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: keyed by HLO hash, so identical operator
# pipelines hit the disk cache across queries, operator instances, AND
# processes. On the TPU every program holding a large ``lax.sort`` takes
# minutes to compile, so this cache decides whether a second process
# starts in seconds or in an hour. Placement rule: where
# JAX_COMPILATION_CACHE_DIR is set, jax has already read it and no
# directory is set in code; where it is not, ONE fixed path inside the
# checkout (git-ignored) — the path is part of the cache key, so a
# directory that moves never hits.
XLA_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".xla_cache")

if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    try:
        _os.makedirs(XLA_CACHE_DIR, exist_ok=True)
        _jax.config.update("jax_compilation_cache_dir", XLA_CACHE_DIR)
    except OSError:  # read-only checkout: run without a disk cache
        pass
try:
    _min_compile_secs = float(
        _os.environ.get("BALLISTA_XLA_CACHE_MIN_COMPILE_SECS", "0"))
except ValueError:
    _min_compile_secs = 0.0
# default 0: cache EVERY kernel. jax's own 1 s floor silently excludes
# small kernels from the disk cache, so they recompile in every fresh
# process — exactly the per-shape cold-path cost the shape-bucket ladder
# exists to amortize. Raise via BALLISTA_XLA_CACHE_MIN_COMPILE_SECS if
# cache-dir churn matters more than cold-start latency.
_jax.config.update("jax_persistent_cache_min_compile_time_secs",
                   _min_compile_secs)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

BALLISTA_TPU_VERSION = "0.2.0"

from .datatypes import (  # noqa: E402
    Boolean,
    DataType,
    Date32,
    Decimal,
    Field,
    Float32,
    Float64,
    Int32,
    Int64,
    Schema,
    Utf8,
    schema,
)
from .columnar import Column, ColumnBatch, Dictionary  # noqa: E402
from .expr import (  # noqa: E402
    avg,
    case,
    col,
    count,
    count_distinct,
    date_lit,
    lit,
    max_,
    min_,
    sum_,
)
from .errors import BallistaError  # noqa: E402


def print_version() -> None:
    print(f"ballista-tpu version: {BALLISTA_TPU_VERSION}")
