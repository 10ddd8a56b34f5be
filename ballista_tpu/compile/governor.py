"""Unified process-wide compile cache + compile observability.

Before this subsystem, every operator kept its own ad-hoc jit dict
(``self._jit_cache`` / ``self._jit_probe`` / module-level ``*_JITS``
maps), so adaptive re-planning — which rebuilds operator instances —
threw away every trace, and nobody could say how much of a query was
compile time. The governor replaces them all:

- **One cache.** ``governed(key, build)`` returns the process-wide
  compiled callable for ``key``; the first caller's ``build()`` supplies
  the python function and the governor owns the single ``jax.jit`` call
  in the codebase (``dev/check_jit_sites.py`` lints that this stays
  true). Keys start with a namespace string and must capture everything
  the trace reads from Python state (operator signatures — see
  ``keys.py``); anything read from *traced arguments* is re-specialized
  by jax itself, so it never belongs in the key.
- **Observability.** A ``jax.monitoring`` listener attributes backend
  compiles (count + seconds) and persistent-cache hits to the governed
  call that triggered them: per-operator ``compile_count`` /
  ``elapsed_compile`` land on the caller's MetricsSet (so EXPLAIN
  ANALYZE shows them), ``BALLISTA_TRACE`` gets a ``compile.jit`` span,
  and :func:`compile_stats` exposes the process-wide totals.
- **Every launch is a span.** ``call_with``, the one way to a program,
  opens ``launch`` with ``site=<program name>`` around the jitted call:
  counted in ``tracing.span_totals()`` under ``launch:jit_<family>`` and
  an annotation of that name in a profiler trace, never a ring record.
  Its seconds are the HOST's, from the call to the return of the jitted
  function (jax's dispatch, output allocation, the enqueue); not the
  program's run on the device, which is asynchronous, except where the
  runtime blocks inside the call. A call that compiled or read the
  persistent cache is tallied under ``launch.cold:jit_<family>``
  instead, so a window's ``launch:*`` delta is warm dispatch only and
  ``compile.jit`` stays the record of the rest.
- **Bounded namespaces.** Mesh-path entries key on pytree structures
  that pin per-query ``Dictionary`` objects; their namespaces carry an
  LRU cap exactly like the bounded dicts they replaced.
"""

from __future__ import annotations

import functools
import re
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

from ..observability.tracing import trace_event, trace_span

__all__ = [
    "MESH_NS_CAP",
    "GovernedFunction",
    "CompileGovernor",
    "governed",
    "governor",
    "compile_stats",
    "reset_compile_stats",
]

# LRU bound for mesh-path namespaces (mesh.compact / mesh.chain /
# mesh.agg_spmd / mesh.join_spmd / mesh.replicate / mesh.run_spmd):
# their keys hold meshes and pytree structures whose aux-data pins
# identity-hashed per-query Dictionary objects, so they stay much
# tighter than the generic JIT_CACHE_ENTRIES bound.
MESH_NS_CAP = 32

# process-wide totals (plain ints/floats under the GIL — same benign-race
# policy as observability.metrics counters)
_STATS: Dict[str, Any] = {
    "backend_compiles": 0,      # actual XLA backend compilations
    "compile_seconds": 0.0,     # time inside those compilations
    "trace_seconds": 0.0,       # jaxpr tracing time (re-traces included)
    "persistent_cache_hits": 0,  # disk-cache hits that skipped a compile
    "governed_calls": 0,        # calls through governed functions
    "entry_hits": 0,            # governed-key lookups that found an entry
    "entries_built": 0,         # governed-key lookups that built one
    "entry_trace_evictions": 0,  # within-entry jax trace-cache clears
}

_tls = threading.local()


class _Frame:
    """Per-governed-call attribution frame (thread-local stack)."""

    __slots__ = ("compiles", "compile_secs", "pcache_hits")

    def __init__(self):
        self.compiles = 0
        self.compile_secs = 0.0
        self.pcache_hits = 0


_listener_lock = threading.Lock()
_listener_registered = False
# False once registration failed: compile accounting then falls back to
# first-call wall-clock per entry (the pre-governor approximation)
_monitoring_ok = True


def _ensure_listener() -> None:
    global _listener_registered, _monitoring_ok
    if _listener_registered:
        return
    with _listener_lock:
        if _listener_registered:
            return

        def on_duration(name: str, secs: float, **kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                _STATS["backend_compiles"] += 1
                _STATS["compile_seconds"] += secs
                f = getattr(_tls, "frame", None)
                if f is not None:
                    f.compiles += 1
                    f.compile_secs += secs
            elif name == "/jax/core/compile/jaxpr_trace_duration":
                _STATS["trace_seconds"] += secs

        def on_event(name: str, **kw) -> None:
            if name == "/jax/compilation_cache/cache_hits":
                _STATS["persistent_cache_hits"] += 1
                f = getattr(_tls, "frame", None)
                if f is not None:
                    f.pcache_hits += 1

        try:
            # the registration calls sit INSIDE the guard: a jax where
            # monitoring imports but lacks/renamed the register_*
            # functions must degrade to fallback mode, not crash every
            # governed call
            from jax import monitoring

            monitoring.register_event_duration_secs_listener(on_duration)
            monitoring.register_event_listener(on_event)
        except Exception:  # noqa: BLE001 - no monitoring: fallback mode
            import warnings

            _monitoring_ok = False
            warnings.warn(
                "jax.monitoring unavailable: compile counts fall back to "
                "first-call wall-clock per governed entry",
                RuntimeWarning, stacklevel=3)
        _listener_registered = True


class GovernedFunction:
    """One governed compile-cache entry: a ``jax.jit`` wrapper plus
    per-entry compile accounting. Shared across operator instances with
    the same signature — jax's own trace cache (keyed on treedef/avals)
    handles shape and dictionary variation within the entry."""

    __slots__ = ("key", "fn", "program", "calls", "compiles",
                 "compile_seconds", "pcache_hits")

    def __init__(self, key: tuple, fn: Callable):
        self.key = key
        self.fn = fn
        # the launch span's site: what a device trace calls the program
        self.program = "jit_" + program_name(key)
        self.calls = 0
        self.compiles = 0
        self.compile_seconds = 0.0
        self.pcache_hits = 0

    def __call__(self, *args, **kwargs):
        return self.call_with(None, *args, **kwargs)

    # Within-entry trace growth bound: jax's jit cache inside one entry
    # specializes per treedef, and treedefs carry identity-hashed
    # per-query Dictionary objects — a stable-keyed entry re-run over
    # refreshed data would otherwise accumulate one executable (and pin
    # one run's string tables) per run, forever. Checked every
    # _TRACE_CHECK_EVERY calls; clearing drops in-memory traces only
    # (the persistent disk cache still holds the compilations).
    _TRACE_CHECK_EVERY = 64

    _TRACES_PER_ENTRY = 128

    def _maybe_trim_traces(self) -> None:
        if self.calls % self._TRACE_CHECK_EVERY:
            return
        try:
            if self.fn._cache_size() > self._TRACES_PER_ENTRY:
                self.fn._clear_cache()
                _STATS["entry_trace_evictions"] += 1
        except Exception:  # noqa: BLE001 - private jax API drifted
            pass

    def call_with(self, metrics, *args, **kwargs):
        """Invoke, attributing any compile this call triggers to
        ``metrics`` (an observability MetricsSet, or None). The call is
        a ``launch`` span (module docstring): in the totals and the
        profiler's trace, never in the ring, which a served query would
        turn over with launches alone."""
        _STATS["governed_calls"] += 1
        self.calls += 1
        self._maybe_trim_traces()
        prev = getattr(_tls, "frame", None)
        frame = _Frame()
        _tls.frame = frame
        span = trace_span("launch", site=self.program)
        span.record = False
        span.__enter__()
        try:
            return self.fn(*args, **kwargs)
        finally:
            _tls.frame = prev
            # no monitoring events on this jax: approximate with the
            # entry's first call (includes that call's execution, like
            # the old PipelineOp measurement did)
            guessed = not _monitoring_ok and self.calls == 1
            cold = guessed or frame.compiles or frame.pcache_hits
            if cold:
                span.name = "launch.cold"
            span.__exit__(None, None, None)
            if guessed:
                frame.compiles = 1
                frame.compile_secs = span.dur
                _STATS["backend_compiles"] += 1
                _STATS["compile_seconds"] += frame.compile_secs
            # a pure disk-cache hit compiles nothing but still traced,
            # lowered and deserialized — record it too, or the warm-disk
            # cold start (the scenario this subsystem optimizes) shows
            # zero compile activity in EXPLAIN ANALYZE
            if cold:
                self._record(frame, span.dur, metrics)

    def _record(self, frame: _Frame, call_secs: float, metrics) -> None:
        self.compiles += frame.compiles
        self.compile_seconds += frame.compile_secs
        self.pcache_hits += frame.pcache_hits
        if metrics is not None:
            # elapsed_compile is the whole first call (upper bound: it
            # includes the first batch's execution, but compile dominates
            # by orders of magnitude on a persistent-cache miss).
            if frame.compiles:
                metrics.add_counter("compile_count", frame.compiles)
            metrics.add_time("elapsed_compile", call_secs)
            if frame.pcache_hits:
                metrics.add_counter("persistent_cache_hits",
                                    frame.pcache_hits)
        trace_event("compile.jit", key=_render_key(self.key),
                    compiles=frame.compiles,
                    compile_seconds=round(frame.compile_secs, 6),
                    persistent_cache_hits=frame.pcache_hits,
                    call_seconds=round(call_secs, 6))


class _BoundGoverned:
    """A governed function bound to one operator's MetricsSet."""

    __slots__ = ("gf", "metrics")

    def __init__(self, gf: GovernedFunction, metrics):
        self.gf = gf
        self.metrics = metrics

    def __call__(self, *args, **kwargs):
        return self.gf.call_with(self.metrics, *args, **kwargs)


def program_name(key: tuple) -> str:
    """The name a governed key gives its program: the key's family (its
    leading namespace string, ``join.unique`` -> ``join_unique``), so an
    XLA module and a device trace read ``jit_join_unique`` where the
    built function was an inner ``run``. No shapes, no literals; under
    40 characters."""
    slug = re.sub(r"[^a-z0-9]+", "_", str(key[0]).lower() if key else "")
    return slug.strip("_")[:39] or "governed"


def _named(fn: Callable, key: tuple) -> Callable:
    """``fn`` under its key's program name. A wrapper, not a rename: a
    build may return a function that others share (a kernel module's
    ``build_dense``), and jax reads the name when it traces."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = program_name(key)
    return program


def _render_key(key: tuple) -> str:
    try:
        return repr(key)[:200]
    except Exception:  # noqa: BLE001 - unreprable key component
        return str(key[0]) if key else "?"


# Default per-namespace LRU bound. Governed entries outlive operator
# instances (that's the point), so a long-lived server answering
# thousands of DISTINCT query shapes would otherwise pin executables —
# and, through treedef keys, per-query dictionaries — forever. 1024 is
# far above any single workload's entry count (the whole TPC-H suite
# builds a few hundred).
JIT_CACHE_ENTRIES = 1024


class CompileGovernor:
    """Process-wide registry of governed compile entries, grouped by the
    key's leading namespace string (per-namespace LRU caps)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._spaces: Dict[str, OrderedDict] = {}
        self._caps: Dict[str, int] = {}

    def get(self, key: tuple, build: Callable[[], Callable], *,
            metrics=None, cap: Optional[int] = None,
            jit_kwargs: Optional[dict] = None):
        """The governed function for ``key`` (built via ``build()`` and
        jitted on first use). ``cap`` bounds the key's namespace (LRU).
        With ``metrics``, returns a bound wrapper that attributes
        compiles to that MetricsSet."""
        _ensure_listener()
        ns = key[0] if key else "default"
        with self._lock:
            space = self._spaces.get(ns)
            if space is None:
                space = self._spaces[ns] = OrderedDict()
            if cap is not None:
                self._caps[ns] = cap
            gf = space.get(key)
            if gf is not None:
                space.move_to_end(key)
                _STATS["entry_hits"] += 1
        if gf is None:
            # build OUTSIDE the lock: build() may itself request governed
            # entries (e.g. a mesh SPMD program wrapping an aggregate's
            # grouped kernel), which would deadlock a held non-reentrant
            # lock. Racing builders are possible and cheap (jit wrapper
            # creation traces nothing); the first insert wins.
            import jax

            gf = GovernedFunction(key, jax.jit(_named(build(), key),
                                               **(jit_kwargs or {})))
            with self._lock:
                # re-fetch: clear() may have swapped the namespace dict
                # while we were building — inserting into the captured
                # (orphaned) dict would silently lose the entry
                space = self._spaces.setdefault(ns, OrderedDict())
                existing = space.get(key)
                if existing is not None:
                    gf = existing
                    space.move_to_end(key)
                    _STATS["entry_hits"] += 1
                else:
                    ns_cap = self._caps.get(ns, JIT_CACHE_ENTRIES)
                    if ns_cap > 0:
                        while len(space) >= ns_cap:
                            space.popitem(last=False)
                    space[key] = gf
                    _STATS["entries_built"] += 1
        if metrics is None:
            return gf
        return _BoundGoverned(gf, metrics)

    def entries(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._spaces.values())

    def entry_rows(self) -> list:
        """Per-entry accounting rows for ``system.compile``: signature,
        call/compile counts, elapsed compile seconds, persistent-cache
        hits. Snapshot under the lock; rendering outside."""
        with self._lock:
            snap = [(ns, gf) for ns, space in self._spaces.items()
                    for gf in space.values()]
        out = []
        for ns, gf in snap:
            out.append({
                "namespace": ns,
                "signature": _render_key(gf.key),
                "calls": gf.calls,
                "compiles": gf.compiles,
                "compile_seconds": round(gf.compile_seconds, 6),
                "persistent_cache_hits": gf.pcache_hits,
            })
        return out

    def namespace_sizes(self) -> Dict[str, int]:
        with self._lock:
            return {ns: len(s) for ns, s in self._spaces.items()}

    def clear(self, namespace: Optional[str] = None) -> None:
        """Drop entries (tests / memory pressure). Compiled executables
        are released; the persistent disk cache still holds them."""
        with self._lock:
            if namespace is None:
                self._spaces.clear()
            else:
                self._spaces.pop(namespace, None)


_GOVERNOR = CompileGovernor()


def governor() -> CompileGovernor:
    return _GOVERNOR


def governed(key: tuple, build: Callable[[], Callable], *, metrics=None,
             cap: Optional[int] = None,
             jit_kwargs: Optional[dict] = None):
    """Module-level shorthand for ``governor().get(...)``."""
    return _GOVERNOR.get(key, build, metrics=metrics, cap=cap,
                         jit_kwargs=jit_kwargs)


def compile_stats() -> Dict[str, Any]:
    """Snapshot of process-wide compile accounting."""
    _ensure_listener()
    out = dict(_STATS)
    out["entries"] = _GOVERNOR.entries()
    out["monitoring_available"] = _monitoring_ok
    return out


def reset_compile_stats() -> None:
    """Zero the process-wide counters (tests; entries stay cached)."""
    for k, v in list(_STATS.items()):
        _STATS[k] = 0.0 if isinstance(v, float) else 0
