#!/usr/bin/env python
"""Compare two bench.py JSON lines and fail on regression.

Intended invocation — OLD is the accepted baseline round, NEW is the
candidate (each file holds one or more JSON lines as bench.py prints
them; the LAST well-formed line wins):

    python dev/check_bench_regress.py OLD.json NEW.json

Exit codes: 0 = no regression, 1 = at least one metric regressed past
its tolerance, 2 = usage / unreadable input. Each checked metric prints
one line (`ok` / `REGRESSED` / `skipped` when either side lacks it), so
a red run says exactly which lane or latency moved.

Per-metric tolerances are deliberately loose: bench runs on a noisy
shared box (the repo's measured run-to-run jitter on cold phases is
tens of percent), so only moves beyond the listed relative slack fail.
Scale them all at once with ``--tolerance-scale`` (e.g. 2.0 on a
particularly noisy box). Metrics the profiler added in PR 7
(``device_blocked_seconds`` / ``host_dictionary_seconds`` /
``compile_trace_lower_seconds``) make ROADMAP's lane-cited targets
(e.g. item 2's host_dictionary < 0.5s) regression-checkable from bench
output alone.

``--self-test`` runs the built-in check of the comparison logic
(tier-1 invokes it from tests/test_distributed_profiler.py).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Optional, Tuple

# metric -> (direction, relative tolerance). "lower" = lower is better.
METRICS: Dict[str, Tuple[str, float]] = {
    # headline throughput (rows/s, higher is better)
    "value": ("higher", 0.25),
    # latencies (seconds, lower is better)
    "warm_seconds": ("lower", 0.25),
    "cold_seconds": ("lower", 0.35),
    "first_run_seconds": ("lower", 0.35),
    "q5_first_seconds": ("lower", 0.35),
    "q5_warm_seconds": ("lower", 0.30),
    "q3_first_seconds": ("lower", 0.35),
    "q3_warm_seconds": ("lower", 0.30),
    "q18_first_seconds": ("lower", 0.35),
    "q18_warm_seconds": ("lower", 0.30),
    "q16_first_seconds": ("lower", 0.35),
    "q16_warm_seconds": ("lower", 0.30),
    # profiler lanes (PR 7; unprefixed = q5, PR 8 added q3/q18): the
    # ROADMAP's lane-cited targets
    "device_blocked_seconds": ("lower", 0.45),
    "host_dictionary_seconds": ("lower", 0.45),
    "compile_trace_lower_seconds": ("lower", 0.45),
    "q3_device_blocked_seconds": ("lower", 0.45),
    "q3_host_dictionary_seconds": ("lower", 0.45),
    "q3_compile_trace_lower_seconds": ("lower", 0.45),
    "q18_device_blocked_seconds": ("lower", 0.45),
    "q18_host_dictionary_seconds": ("lower", 0.45),
    "q18_compile_trace_lower_seconds": ("lower", 0.45),
    # PR 11 (dictionary registry): q16 is the string-heavy join query
    # pinning the host_dictionary lane — it may never silently regrow
    "q16_device_blocked_seconds": ("lower", 0.45),
    "q16_host_dictionary_seconds": ("lower", 0.45),
    "q16_compile_trace_lower_seconds": ("lower", 0.45),
    # resource envelope
    "peak_rss_mb": ("lower", 0.30),
    # live progress plane (PR 10): on_progress callbacks delivered
    # during the cold q5 run — a sampler that silently dies would read
    # 0. "nonzero": only 0 regresses. The raw count scales with cold-run
    # wall time, so a ratio gate would punish legitimate cold-time
    # speedups. Absent from pre-PR-10 baselines (compare() skips
    # missing keys).
    "progress_samples": ("nonzero", 0.0),
    # PR 12 (memory-governed streaming shuffle): the fixed-budget q5
    # cluster run. spill_bytes reads 0 if the spill lane silently dies;
    # the in-flight peak and the run's RSS must not regrow round-over-
    # round (the ABSOLUTE peak<=budget gate is budget_check below).
    "spill_bytes": ("nonzero", 0.0),
    "shuffle_peak_inflight_mb": ("lower", 0.50),
    "spill_q5_seconds": ("lower", 0.50),
    "spill_q5_peak_rss_mb": ("lower", 0.35),
    # PR 15 (admission plane): bench_serving.py — K concurrent mixed
    # TPC-H sessions against one warm LocalCluster. Throughput rides
    # "value" (higher) in that file; the latency percentiles must not
    # silently regrow round-over-round, and an engine error during the
    # storm (sheds are counted separately and are policy, not errors)
    # shows up as serving_completed dropping to 0.
    "serving_p50_seconds": ("lower", 0.40),
    "serving_p99_seconds": ("lower", 0.50),
    "serving_completed": ("nonzero", 0.0),
    # engine errors during the storm must stay ZERO (sheds are counted
    # separately — they are policy, not errors)
    "serving_errors": ("zero", 0.0),
    # PR 20 (latency ledger, docs/observability.md): the serving line
    # carries per-lane p50/p99 from the always-on per-query ledger. The
    # dominant lanes must not silently regrow (generous tolerance —
    # single-lane seconds are noisier than the end-to-end percentile),
    # and a storm that records no ledgers means the always-on
    # attribution plane is dead. Zero-baseline lanes (a workload that
    # never queued, say) are skipped by the o<=0 ratio-gate rule.
    "serving_ledgers": ("nonzero", 0.0),
    "serving_device_execute_p99_seconds": ("lower", 0.60),
    "serving_compile_p99_seconds": ("lower", 0.60),
    "serving_planning_p99_seconds": ("lower", 0.60),
    "serving_queue_wait_p99_seconds": ("lower", 0.60),
    "serving_shuffle_fetch_p99_seconds": ("lower", 0.60),
    # PR 17 (durable control plane): bench_serving.py --phase restart
    # times the rehydrate+recover gap of a scheduler restart over
    # sqlite; recovered_jobs reads 0 if the journal or the recovery
    # pass silently dies, and recovery errors are never acceptable.
    "recovery_seconds": ("lower", 0.50),
    "recovered_jobs": ("nonzero", 0.0),
    "recovery_errors": ("zero", 0.0),
    # --phase autoscale storms a min-sized fleet at 2x sessions: a
    # burst that triggers no scaling decision means the loop is dead,
    # and the burst's tail latency must not silently regrow.
    "autoscale_events": ("nonzero", 0.0),
    "autoscale_p99_seconds": ("lower", 0.50),
    "autoscale_errors": ("zero", 0.0),
    # PR 19 (warm-path serving caches, docs/caching.md): the cache
    # phase repeats q1 on a fresh residency tier. Warm/hit latencies
    # and speedups must not silently regrow; the per-line counters and
    # the byte-identity / budget-respect flags are aliveness gates (a
    # cache that silently stops hitting, donating or evicting reads 0).
    "cache_warm_q1_seconds": ("lower", 0.40),
    "cache_q1_speedup": ("higher", 0.40),
    "result_cache_hit_seconds": ("lower", 0.50),
    "result_cache_speedup": ("higher", 0.50),
    "table_cache_hits": ("nonzero", 0.0),
    "result_cache_hits": ("nonzero", 0.0),
    "donated_buffers": ("nonzero", 0.0),
    "cache_q1_identical": ("nonzero", 0.0),
    "result_cache_identical": ("nonzero", 0.0),
    "cache_budget_identical": ("nonzero", 0.0),
    "cache_budget_ok": ("nonzero", 0.0),
    "cache_budget_evictions": ("nonzero", 0.0),
}


def budget_check(new: dict) -> int:
    """Absolute gate for the fixed-budget q5 run: the governed in-flight
    peak must respect the configured shuffle memory budget (plus one
    chunk of slack — a charge is refused only once it would CROSS the
    watermark). Returns the number of violations."""
    peak = new.get("shuffle_peak_inflight_mb")
    budget = new.get("spill_budget_mb")
    if peak is None or budget is None:
        return 0
    slack = float(new.get("spill_chunk_mb", 4.0))
    if float(peak) > float(budget) + slack:
        print(f"regressed  shuffle_peak_inflight_mb: {peak} MB exceeds "
              f"the configured budget {budget} MB (+{slack} MB chunk "
              "slack)")
        return 1
    print(f"ok         shuffle_peak_inflight_mb: {peak} MB within "
          f"budget {budget} MB")
    return 0


def last_json_line(path: str) -> Optional[dict]:
    """The bench line in the file. Accepts both raw bench.py output
    (JSON lines; the LAST well-formed one wins) and the
    driver's archived wrapper format (BENCH_rNN.json: one pretty-printed
    object with the bench line under ``parsed``)."""
    try:
        text = open(path).read()
    except OSError as e:
        print(f"error: cannot read {path}: {e}", file=sys.stderr)
        return None
    try:
        whole = json.loads(text)
        if isinstance(whole, dict):
            if isinstance(whole.get("parsed"), dict):
                return whole["parsed"]
            return whole
    except ValueError:
        pass
    for line in reversed(text.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    print(f"error: no JSON line in {path}", file=sys.stderr)
    return None


def compare(old: dict, new: dict, tolerance_scale: float = 1.0) -> list:
    """Returns [(metric, old, new, rel_change, regressed, checked)].
    ``rel_change`` is signed so the report reads naturally: positive =
    the metric moved in the WORSE direction."""
    rows = []
    for metric, (direction, tol) in METRICS.items():
        if metric not in old or metric not in new:
            rows.append((metric, old.get(metric), new.get(metric),
                         None, False, False))
            continue
        o, n = float(old[metric]), float(new[metric])
        if direction == "nonzero":
            # aliveness gate: regress only when a previously-reporting
            # metric reads 0 now (magnitude is wall-time-coupled noise)
            regressed = o > 0 and n <= 0
            rows.append((metric, o, n, 1.0 if regressed else 0.0,
                         regressed, True))
            continue
        if direction == "zero":
            # hard-zero gate: any nonzero NEW value regresses (the old
            # value is irrelevant — errors are never acceptable)
            regressed = n > 0
            rows.append((metric, o, n, 1.0 if regressed else 0.0,
                         regressed, True))
            continue
        if o <= 0:
            rows.append((metric, o, n, None, False, False))
            continue
        if direction == "lower":
            rel = (n - o) / o  # got slower/bigger = worse
        else:
            rel = (o - n) / o  # got smaller = worse
        regressed = rel > tol * tolerance_scale
        rows.append((metric, o, n, rel, regressed, True))
    return rows


def report(rows, tolerance_scale: float) -> int:
    bad = 0
    for metric, o, n, rel, regressed, checked in rows:
        if not checked:
            print(f"skipped    {metric}: missing on one side "
                  f"(old={o!r} new={n!r})")
            continue
        direction, tol = METRICS[metric]
        tol *= tolerance_scale
        tag = "REGRESSED" if regressed else "ok"
        if regressed:
            bad += 1
        print(f"{tag:<10} {metric}: {o:g} -> {n:g} "
              f"({rel:+.1%} worse-direction, tol {tol:.0%}, "
              f"{direction} is better)")
    if bad:
        print(f"{bad} metric(s) regressed past tolerance",
              file=sys.stderr)
    return 1 if bad else 0


def self_test() -> int:
    """Pin the comparison semantics this script promises."""
    old = {"value": 1000.0, "warm_seconds": 1.0,
           "host_dictionary_seconds": 2.0, "peak_rss_mb": 1000.0}
    # within tolerance: slightly slower warm, slightly lower throughput
    ok_new = {"value": 900.0, "warm_seconds": 1.1,
              "host_dictionary_seconds": 1.0, "peak_rss_mb": 1100.0}
    rows = compare(old, ok_new)
    assert not any(r[4] for r in rows), rows
    # a big warm slowdown regresses; an IMPROVEMENT never does
    bad_new = {"value": 5000.0, "warm_seconds": 2.0}
    rows = {r[0]: r for r in compare(old, bad_new)}
    assert rows["warm_seconds"][4] is True
    assert rows["value"][4] is False
    # higher-is-better: a big throughput drop regresses
    rows = {r[0]: r for r in compare(old, {"value": 500.0})}
    assert rows["value"][4] is True
    # missing metrics are skipped, never failed
    assert all(not r[4] for r in compare(old, {}))
    # tolerance scaling loosens the gate
    rows = {r[0]: r for r in compare(old, {"warm_seconds": 1.4},
                                     tolerance_scale=2.0)}
    assert rows["warm_seconds"][4] is False
    # zero/absent baselines are skipped (cannot compute a ratio)
    assert not any(r[4] for r in compare({"value": 0.0},
                                         {"value": 10.0}))
    # nonzero metrics: only a drop to 0 regresses — a faster cold run
    # delivering FEWER samples must never fail the gate
    rows = {r[0]: r for r in compare({"progress_samples": 8},
                                     {"progress_samples": 2})}
    assert rows["progress_samples"][4] is False
    rows = {r[0]: r for r in compare({"progress_samples": 8},
                                     {"progress_samples": 0})}
    assert rows["progress_samples"][4] is True
    # absolute budget gate: in-flight peak past budget+chunk fails,
    # within it passes, absent fields are a no-op
    assert budget_check({"shuffle_peak_inflight_mb": 7.5,
                         "spill_budget_mb": 8.0,
                         "spill_chunk_mb": 1.0}) == 0
    assert budget_check({"shuffle_peak_inflight_mb": 20.0,
                         "spill_budget_mb": 8.0,
                         "spill_chunk_mb": 1.0}) == 1
    assert budget_check({}) == 0
    # zero metrics: ANY nonzero new value regresses, improvement to 0
    # never does
    rows = {r[0]: r for r in compare({"serving_errors": 0},
                                     {"serving_errors": 2})}
    assert rows["serving_errors"][4] is True
    rows = {r[0]: r for r in compare({"serving_errors": 3},
                                     {"serving_errors": 0})}
    assert rows["serving_errors"][4] is False
    # restart phase: recovery_seconds is lower-is-better — a FASTER
    # recovery must never regress, a 2x slower one must
    rows = {r[0]: r for r in compare({"recovery_seconds": 2.0},
                                     {"recovery_seconds": 0.5})}
    assert rows["recovery_seconds"][4] is False
    rows = {r[0]: r for r in compare({"recovery_seconds": 1.0},
                                     {"recovery_seconds": 2.0})}
    assert rows["recovery_seconds"][4] is True
    # recovered_jobs / autoscale_events are aliveness gates: only a
    # drop to 0 regresses (fewer jobs in the batch is configuration)
    rows = {r[0]: r for r in compare({"recovered_jobs": 6},
                                     {"recovered_jobs": 0})}
    assert rows["recovered_jobs"][4] is True
    rows = {r[0]: r for r in compare({"autoscale_events": 4},
                                     {"autoscale_events": 1})}
    assert rows["autoscale_events"][4] is False
    # recovery/autoscale errors: hard zero
    rows = {r[0]: r for r in compare({"recovery_errors": 0},
                                     {"recovery_errors": 1})}
    assert rows["recovery_errors"][4] is True
    # cache phase (PR 19): warm latency is lower-is-better, speedup is
    # higher-is-better — a faster warm run / bigger speedup never fails
    rows = {r[0]: r for r in compare(
        {"cache_warm_q1_seconds": 0.10, "cache_q1_speedup": 10.0},
        {"cache_warm_q1_seconds": 0.30, "cache_q1_speedup": 2.0})}
    assert rows["cache_warm_q1_seconds"][4] is True
    assert rows["cache_q1_speedup"][4] is True
    rows = {r[0]: r for r in compare(
        {"result_cache_hit_seconds": 0.05, "result_cache_speedup": 5.0},
        {"result_cache_hit_seconds": 0.01, "result_cache_speedup": 50.0})}
    assert not any(r[4] for r in rows.values())
    # identity / budget-respect flags and the live counters are
    # aliveness gates: a drop to 0 regresses, a smaller count does not
    rows = {r[0]: r for r in compare(
        {"cache_q1_identical": 1, "cache_budget_ok": 1,
         "donated_buffers": 18, "table_cache_hits": 4},
        {"cache_q1_identical": 0, "cache_budget_ok": 1,
         "donated_buffers": 0, "table_cache_hits": 1})}
    assert rows["cache_q1_identical"][4] is True
    assert rows["cache_budget_ok"][4] is False
    assert rows["donated_buffers"][4] is True
    assert rows["table_cache_hits"][4] is False
    # ledger lanes (PR 20): lower-is-better with a generous tolerance —
    # a lane p99 that more than doubles regresses, one that shrinks
    # never does, and a zero-baseline lane (never exercised) is skipped
    # rather than tripping a divide-by-zero ratio
    rows = {r[0]: r for r in compare(
        {"serving_device_execute_p99_seconds": 1.0,
         "serving_compile_p99_seconds": 0.5,
         "serving_queue_wait_p99_seconds": 0.0},
        {"serving_device_execute_p99_seconds": 2.5,
         "serving_compile_p99_seconds": 0.2,
         "serving_queue_wait_p99_seconds": 0.4})}
    assert rows["serving_device_execute_p99_seconds"][4] is True
    assert rows["serving_compile_p99_seconds"][4] is False
    assert rows["serving_queue_wait_p99_seconds"][5] is False  # skipped
    # serving_ledgers is an aliveness gate: the always-on plane going
    # silent regresses; recording fewer ledgers does not
    rows = {r[0]: r for r in compare({"serving_ledgers": 24},
                                     {"serving_ledgers": 0})}
    assert rows["serving_ledgers"][4] is True
    rows = {r[0]: r for r in compare({"serving_ledgers": 24},
                                     {"serving_ledgers": 6})}
    assert rows["serving_ledgers"][4] is False
    print("self-test ok")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(
        description="compare two bench.py JSON files; non-zero exit on "
                    "regression")
    ap.add_argument("old", nargs="?", help="baseline bench JSON file")
    ap.add_argument("new", nargs="?", help="candidate bench JSON file")
    ap.add_argument("--tolerance-scale", type=float, default=1.0,
                    help="multiply every per-metric tolerance "
                         "(noisy boxes: try 2.0)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in comparison-logic checks")
    args = ap.parse_args()
    if args.self_test:
        return self_test()
    if not args.old or not args.new:
        ap.print_usage(sys.stderr)
        return 2
    old = last_json_line(args.old)
    new = last_json_line(args.new)
    if old is None or new is None:
        return 2
    rc = report(compare(old, new, args.tolerance_scale),
                args.tolerance_scale)
    return rc or (1 if budget_check(new) else 0)


if __name__ == "__main__":
    sys.exit(main())
