"""sync-span: implicit host syncs on device values must sit inside a
``device.block`` tracing span.

The profiler's lane decomposition (docs/observability.md) attributes
query wall time to lanes; ``device_blocked`` is computed as the sum of
``device.block`` spans, so a blocking sync OUTSIDE such a span silently
shifts device time into whatever lane encloses it — exactly the class
of skew PR 5/7 review rounds kept fixing by hand. This pass makes the
attribution honest by construction.

Candidate sync sites:

- ``jax.device_get(...)`` — the explicit D2H fetch;
- ``<x>.item()`` — scalar host read (numpy's is host-only; suppress
  with a reason where the receiver provably never holds a jax array);
- ``np.asarray(X)`` where ``X`` is *device-provenance*: an attribute
  read of a ColumnBatch/Column device buffer (``.values`` /
  ``.validity`` / ``.selection``), or a local name assigned from a
  ``jax.*`` / ``jnp.*`` call or such an attribute. Host-side
  ``np.asarray`` over parsed python lists/numpy inputs is NOT flagged
  — provenance, not the call, is what makes it a sync.

A candidate is covered when it sits lexically inside a ``with
trace_span("device.block", ...)`` block (module-local containment —
the span need not be in the same function, a wrapper's span covers the
wrapped body). Everything else is a finding: wrap it with a span
carrying a ``site=`` attribute, or suppress with
``# ballista: ignore[sync-span]`` and a reason.

The ``site=`` of every ``device.block`` span must be a string LITERAL:
it is the span's sub-key in ``tracing.span_totals()``
(``device.block:join.stats``), and a key made from a value of the data,
a job or a task would grow the totals without bound. A span without
one, or with a computed one, is a finding too.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set, Tuple

from ..callgraph import walk_functions
from ..engine import Finding, Package, Rule, SourceFile, make_finding

DEVICE_ATTRS = frozenset({"values", "validity", "selection"})

# jax host-side API: returns python objects, never device arrays — a
# name assigned from these carries NO device provenance
HOST_JAX_CALLS = frozenset({
    "devices", "local_devices", "device_count", "local_device_count",
    "process_index", "process_count", "tree_structure", "tree_flatten",
})

# modules whose whole business is recording/deciding, not executing —
# the span machinery itself must not be asked to span itself
SKIP_FILES = frozenset({
    "ballista_tpu/observability/tracing.py",
})


def _is_block_span(call: ast.AST) -> bool:
    """``trace_span("device.block", ...)``."""
    if not isinstance(call, ast.Call) or not call.args:
        return False
    fname = (call.func.id if isinstance(call.func, ast.Name)
             else call.func.attr
             if isinstance(call.func, ast.Attribute) else "")
    first = call.args[0]
    return fname == "trace_span" and isinstance(first, ast.Constant) \
        and first.value == "device.block"


def _span_ranges(sf: SourceFile) -> List[Tuple[int, int]]:
    """(start, end) line ranges of every ``with trace_span("device.block"
    ...)`` body in the file."""
    ranges: List[Tuple[int, int]] = []
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.With):
            continue
        if any(_is_block_span(item.context_expr) for item in node.items):
            ranges.append((node.lineno, node.end_lineno or node.lineno))
    return ranges


def _literal_site(call: ast.Call) -> bool:
    return any(kw.arg == "site" and isinstance(kw.value, ast.Constant)
               and isinstance(kw.value.value, str) for kw in call.keywords)


def _covered(line: int, ranges: List[Tuple[int, int]]) -> bool:
    return any(lo <= line <= hi for lo, hi in ranges)


class _Provenance:
    """Per-function map of local names assigned from device values."""

    def __init__(self, fn: ast.AST, np_aliases: Set[str],
                 jax_aliases: Set[str]):
        self.np_aliases = np_aliases
        self.jax_aliases = jax_aliases
        self.device_names: Set[str] = set()
        # two passes so order of assignment vs use doesn't matter
        for _ in range(2):
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign):
                    if self.is_device(node.value):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                self.device_names.add(t.id)

    def is_device(self, expr: ast.AST) -> bool:
        expr = self._unwrap(expr)
        if isinstance(expr, ast.Attribute):
            if expr.attr in DEVICE_ATTRS:
                return True
            return False
        if isinstance(expr, ast.Name):
            return expr.id in self.device_names
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self.is_device(e) for e in expr.elts)
        if isinstance(expr, ast.Call):
            f = expr.func
            if isinstance(f, ast.Attribute) and f.attr in HOST_JAX_CALLS:
                return False
            root = f
            while isinstance(root, ast.Attribute):
                root = root.value
            if isinstance(root, ast.Name) and root.id in self.jax_aliases:
                return True
        return False

    @staticmethod
    def _unwrap(expr: ast.AST) -> ast.AST:
        while isinstance(expr, (ast.Subscript, ast.Starred)):
            expr = expr.value
        return expr


class SyncSpanRule(Rule):
    id = "sync-span"
    description = ("implicit device->host syncs must run inside a "
                   "device.block span (profiler lane honesty)")

    def __init__(self, skip_files: Optional[Set[str]] = None):
        self.skip_files = (frozenset(skip_files) if skip_files is not None
                           else SKIP_FILES)

    def _aliases(self, package: Package, rel: str
                 ) -> Tuple[Set[str], Set[str]]:
        mi = package.index().module(rel)
        np_aliases: Set[str] = set()
        jax_aliases: Set[str] = set()
        if mi is None:
            return np_aliases, jax_aliases
        for local in mi.imports:
            root = mi.external_root(local)
            if root == "numpy":
                np_aliases.add(local)
            elif root == "jax":
                jax_aliases.add(local)
        return np_aliases, jax_aliases

    def run(self, package: Package) -> List[Finding]:
        findings: List[Finding] = []
        for sf in package.files:
            if sf.rel in self.skip_files:
                continue
            np_aliases, jax_aliases = self._aliases(package, sf.rel)
            spans = _span_ranges(sf)
            for node in ast.walk(sf.tree):
                if _is_block_span(node) and not _literal_site(node):
                    findings.append(make_finding(
                        self.id, sf, node.lineno,
                        "device.block span whose site= is not a string "
                        "literal (the site is the span's key in "
                        "span_totals(): a literal of the code, never a "
                        "value)"))
            seen: Set[Tuple[int, int]] = set()  # nested defs walk twice
            for fn, _cls in walk_functions(sf):
                prov = _Provenance(fn, np_aliases, jax_aliases)
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    pos = (node.lineno, node.col_offset)
                    if pos in seen:
                        continue
                    hit = self._classify(node, prov, np_aliases,
                                         jax_aliases)
                    if hit is None:
                        continue
                    seen.add(pos)
                    if _covered(node.lineno, spans):
                        continue
                    findings.append(make_finding(
                        self.id, sf, node.lineno,
                        f"{hit} outside a device.block span (wrap with "
                        "trace_span(\"device.block\", site=...) or "
                        "suppress with a reason)"))
        return findings

    def _classify(self, call: ast.Call, prov: _Provenance,
                  np_aliases: Set[str], jax_aliases: Set[str]
                  ) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Attribute):
            base = f.value
            if f.attr == "device_get" and isinstance(base, ast.Name) \
                    and base.id in jax_aliases:
                return "jax.device_get sync"
            if f.attr == "item" and not call.args and not call.keywords:
                return "scalar .item() sync"
            if f.attr == "asarray" and isinstance(base, ast.Name) \
                    and base.id in np_aliases and call.args:
                # dtype=object arrays are host-only by construction
                # (dictionary value tables, not device buffers)
                for kw in call.keywords:
                    if kw.arg == "dtype" and \
                            isinstance(kw.value, ast.Name) and \
                            kw.value.id == "object":
                        return None
                if prov.is_device(call.args[0]):
                    return "np.asarray on a device value"
        elif isinstance(f, ast.Name) and f.id == "device_get":
            return "device_get sync"
        return None
