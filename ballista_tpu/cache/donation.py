"""Buffer-donation eligibility tracking for fused execution.

Steady-state execution moves every batch through exactly one governed
XLA program (the fused pipeline chain, or an aggregation program). XLA
can reuse a donated input buffer for the output allocation
(``donate_argnums``), turning the copy-in/copy-out round trip into an
in-place update — but ONLY when the engine can prove the input has
exactly one consumer and nothing else will ever read it again. This
module is that proof:

- A :class:`~ballista_tpu.columnar.ColumnBatch` carries a
  ``_transient`` flag, ``False`` by default. Only the sites that
  CREATE a single-owner batch mark it: scan emission when the batch is
  *not* being pinned by the device table cache, ``concat_batches`` for
  ``len > 1`` (fresh ``jnp.concatenate`` output: a join's build side, a
  sort, a repartition, an aggregate that sorts or whose batches carry
  different dictionaries), and the fused pipeline's and the aggregates'
  per-batch output. Cached / pinned / materialized batches are never
  marked, so they are never donation-eligible by construction.
- An aggregate donates ONE batch that arrives alone and transient
  (``PhysicalPlan.governed_call``). A partition that arrives as several
  batches sharing their dictionaries is handed to the program as a
  tuple and put together inside its trace
  (``HashAggregateExec._partition_input``, ``how=in_program``): no
  concatenated intermediate exists on the host's side to donate, the
  pieces may be pinned, and XLA could not alias a concatenation onto
  its inputs anyway, so that call donates nothing.
- :func:`consume_transient` claims the flag exactly once. A call site
  that donates MUST consume first — a second alias of the same batch
  then sees ``False`` and takes the copying path instead of touching
  deleted buffers.

The ``num_rows`` scalar is NEVER donated even on transient batches:
``MetricsSet.record_output_batch`` holds it in ``_pending_rows`` long
after the batch body is consumed (see ``PhysicalPlan.governed_call``
in ``physical/base.py`` for the split-call wiring).

``BALLISTA_DONATION=off`` disables the whole tier; marked flags are
simply never consumed.
"""

from __future__ import annotations

import os
import threading
import warnings

# Donation is best-effort by design: a program whose output shapes
# don't line up with an input buffer simply allocates (e.g. the 8-slot
# scalar-agg output vs a 2^20-row input). XLA's per-call warning for
# those is noise here, and the interesting number is tracked by
# record_donation instead.
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

_OFF = ("off", "0", "false", "no")


def donation_enabled() -> bool:
    """``BALLISTA_DONATION``: donate single-consumer intermediate
    buffers through governed programs (default on)."""
    return os.environ.get("BALLISTA_DONATION", "on").lower() not in _OFF


def mark_transient(batch) -> None:
    """Mark ``batch`` single-owner: its creator guarantees no other
    reference will read the device buffers after the one consumer."""
    batch._transient = True


def is_transient(batch) -> bool:
    return bool(getattr(batch, "_transient", False))


def propagate_transient(src, dst) -> None:
    """Carry the mark through a pass-through transform (same buffers,
    new wrapper)."""
    if is_transient(src):
        dst._transient = True


def consume_transient(batch) -> bool:
    """Claim the donation right: True exactly once per marked batch.
    Clearing before the donating call means an aliasing second consumer
    can never double-donate the same buffers."""
    if getattr(batch, "_transient", False):
        batch._transient = False
        return True
    return False


_lock = threading.Lock()
_donated_calls = 0
_donated_bytes = 0


def record_donation(nbytes: int) -> None:
    global _donated_calls, _donated_bytes
    with _lock:
        _donated_calls += 1
        _donated_bytes += int(nbytes)


def donation_stats() -> dict:
    with _lock:
        return {
            "donated_buffers": _donated_calls,
            "donated_bytes": _donated_bytes,
            "enabled": donation_enabled(),
        }


def reset_donation_stats() -> None:
    """Re-baseline the cumulative counters (bench phases, tests)."""
    global _donated_calls, _donated_bytes
    with _lock:
        _donated_calls = 0
        _donated_bytes = 0
