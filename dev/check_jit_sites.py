#!/usr/bin/env python
"""Guard against raw ``jax.jit`` call sites regrowing outside the
compile governor — thin shim over the unified analysis engine
(``ballista_tpu/analysis/``, rule id ``jit-sites``; run everything at
once with ``dev/analyze.py``).

CLI and exit semantics are unchanged from the standalone version:
exit 0 = clean, per-site ``JIT-SITE:`` lines on stderr otherwise, and
``--budget`` still runs the program-count regression gate. Per-line
opt-out stays ``# jit-ok: <reason>``; the allowlist lives on the rule
(``analysis/passes/shape.py::JitSitesRule``).

Usage: python dev/check_jit_sites.py   (exit 0 = clean)
"""

from __future__ import annotations

import os
import sys
from typing import List, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.normpath(os.path.join(HERE, ".."))
sys.path.insert(0, HERE)

import analyze  # noqa: E402 - sibling loader for the analysis engine


def scan() -> List[Tuple[str, int, str]]:
    """[(repo-relative file, line, source line)] of violations —
    signature preserved for tests importing this module directly."""
    analysis = analyze.load_analysis(REPO)
    pkg = analysis.Package.load(REPO)
    rule = analysis.RULE_FACTORIES["jit-sites"]()
    result = analysis.analyze(pkg, [rule])
    # unparseable files fail too: the regex original scanned raw text,
    # so a violation in a broken file could never pass silently
    return [(f.file, f.line, f.message) for f in result.parse_errors] + \
        [(f.file, f.line, pkg.by_rel[f.file].line(f.line).rstrip())
         for f in result.findings]


# ---------------------------------------------------------------------------
# program-count regression gate (--budget): whole-stage fusion exists to
# keep the governed program count down; silent de-fusion (a matcher that
# stops firing, a planner change that breaks the chain shape) would leak
# programs back without failing any correctness test. The gate runs
# q1+q5 on a tiny generated dataset with fusion ON and pins (a) that
# fused operators are actually in the plans and (b) the number of
# governed entries minted. Budget pinned from a measured 22 entries
# (pre-fusion: 27 at the same scale) with small headroom for planner
# drift — a de-fused q1 alone would add 3+ entries and trip it. PR 32
# spent the headroom (24 measured) and added one entry on purpose: q5's
# join with a Filter fused into its probe side runs that chain alone
# (``join.prologue``) for its first two batches, to learn whether to
# compact before the probe. 25 measured, so no headroom is left.
# ---------------------------------------------------------------------------

DEFAULT_ENTRY_BUDGET = 25


def check_budget(budget: int = DEFAULT_ENTRY_BUDGET) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["BALLISTA_FUSION"] = "on"
    import tempfile

    sys.path.insert(0, REPO)
    from benchmarks.tpch import datagen
    from benchmarks.tpch.schema_def import register_tpch
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.compile import compile_stats
    from ballista_tpu.physical.fusion import FusedStageExec
    from ballista_tpu.physical.join import JoinExec

    import shutil

    d = tempfile.mkdtemp(prefix="jit_budget_")
    try:
        datagen.generate(d, scale=0.002, num_parts=2)
        ctx = BallistaContext.standalone()
        register_tpch(ctx, d, "tbl")
        qdir = os.path.join(HERE, "..", "benchmarks", "tpch", "queries")
        fused_seen = 0
        for q in ("q1", "q5"):
            df = ctx.sql(open(os.path.join(qdir, f"{q}.sql")).read())
            df.collect()
            phys = df._phys

            def count_fused(node):
                n = int(isinstance(node, FusedStageExec))
                n += int(isinstance(node, JoinExec)
                         and bool(node.probe_chain))
                return n + sum(count_fused(c) for c in node.children())

            fused_seen += count_fused(phys)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    if fused_seen == 0:
        print("BUDGET: no FusedStageExec in the q1+q5 plans — "
              "silent de-fusion", file=sys.stderr)
        return 1
    built = int(compile_stats()["entries_built"])
    if built > budget:
        print(f"BUDGET: q1+q5 minted {built} governed entries "
              f"(budget {budget}) — fusion regressed", file=sys.stderr)
        return 1
    print(f"program budget ok: {built} governed entries <= {budget} "
          f"({fused_seen} fused stages)")
    return 0


def main() -> int:
    if "--budget" in sys.argv:
        i = sys.argv.index("--budget")
        n = (int(sys.argv[i + 1]) if len(sys.argv) > i + 1
             else DEFAULT_ENTRY_BUDGET)
        return check_budget(n)
    hits = scan()
    if hits:
        for rel, i, line in hits:
            print(f"JIT-SITE: {rel}:{i}: {line.strip()}", file=sys.stderr)
        print(
            f"{len(hits)} raw jax.jit call site(s) outside "
            "ballista_tpu/compile/ — route them through "
            "ballista_tpu.compile.governed() (or extend the allowlist "
            "with a justification)",
            file=sys.stderr,
        )
        return 1
    print("no raw jax.jit sites outside ballista_tpu/compile/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
