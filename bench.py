"""ballista-tpu benchmark: TPC-H q1 on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}

Baseline: the reference engine's only published number — TPC-H q1 at SF~1
in 1956.1 ms on a docker-compose cluster (reference:
rust/benchmarks/tpch/README.md:70-84). SF1 lineitem is 6,001,215 rows, so
the reference throughput is ~3.068M rows/s. ``vs_baseline`` compares our
warm end-to-end q1 rows/sec (device-resident cached table, like a Spark
.cache() workload) against that; cold (re-scan per run, like the
reference does) numbers ride along in the extras.

Usage: python bench.py [--scale 1.0] [--data DIR] [--runs 3] [--cpu]

Measures in its OWN process (one process per chip) and fails where JAX
finds no accelerator: ``--cpu`` is the only way onto the CPU, and a CPU
run is not a device measurement. A phase that raises ends the run
non-zero with no metric line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REF_ROWS_PER_SEC = 6_001_215 / 1.9561  # reference q1 SF1 wall time

# Peak dense-compute rates for the MFU estimate, by device_kind substring.
# q1 is integer/VPU-bound, so MFU vs the MXU bf16 peak is structurally
# tiny — the number is a utilization *floor* recorded for trend-tracking,
# with the assumed peak alongside so it can be reinterpreted.
_PEAK_FLOPS = [
    ("v5 lite", 197e12),  # TPU v5e: 197 TFLOP/s bf16
    ("v5e", 197e12),
    ("v4", 275e12),
]


def _peak_flops(device_kind: str) -> float:
    """An accelerator that is not in the table is an error, not a
    default (the CPU backend has no entry: a ``--cpu`` run carries no
    peak-derived field)."""
    dk = device_kind.lower()
    for sub, peak in _PEAK_FLOPS:
        if sub in dk:
            return peak
    raise KeyError(f"no peak FLOP/s on record for device kind "
                   f"{device_kind!r}; add it to bench._PEAK_FLOPS")


def cold_phase_split(run_fn):
    """Run ``run_fn()`` and attribute its wall time across the ingest
    phases (parse / H2D / execute-and-compile remainder) using the
    process-wide accumulators in ballista_tpu.ingest.

    ``parse_seconds``/``h2d_seconds`` are THREAD time: with the ingest
    pipeline ON they overlap each other and device compute, so they can
    legitimately sum past wall time (that overlap IS the win);
    ``execute_seconds`` is the non-ingest remainder of the wall clock,
    clamped at 0. With the pipeline gated off (serial scans) the three
    fields sum to the wall time exactly — the tier-1 smoke test pins
    that identity. Returns ``(run_fn result, phase dict)``."""
    from ballista_tpu.ingest import phase_totals

    p0 = phase_totals()
    t0 = time.time()
    ret = run_fn()
    wall = time.time() - t0
    p1 = phase_totals()
    parse = p1["parse"] - p0["parse"]
    h2d = p1["h2d"] - p0["h2d"]
    return ret, {
        "wall_seconds": round(wall, 4),
        "parse_seconds": round(parse, 4),
        "h2d_seconds": round(h2d, 4),
        "execute_seconds": round(max(wall - parse - h2d, 0.0), 4),
    }


def profiled_query(ctx, name: str, sql: str, runs: int, result: dict,
                   timed, lane_prefix: str,
                   progress_field: str = "") -> None:
    """Shared TPC-H query measurement: the FIRST run executes under a
    profiler window so the named wall-time lanes land in the JSON line
    (`{lane_prefix}device_blocked_seconds` etc. — q5 keeps the
    unprefixed legacy names, q3/q18 prefix theirs), then a warm
    minimum. A failure here ends the run: truncated (artificially good)
    lane values must never be gated against a baseline in
    dev/check_bench_regress.py."""
    from ballista_tpu.observability.export import compute_lanes
    from ballista_tpu.observability.profiler import Profiler

    prof = Profiler(label=f"{name}-first")
    prof.start()
    df = ctx.sql(sql)
    if progress_field:
        # live progress plane: count the on_progress callbacks the
        # first (cold) run delivers — pins that the sampler stays
        # alive on the bench workload (gated as higher-is-better by
        # dev/check_bench_regress.py)
        samples = []
        t0 = time.time()
        df.collect(on_progress=samples.append)
        first = time.time() - t0
        result[progress_field] = len(samples)
    else:
        first = timed(df)  # load + compile
    lane_info = compute_lanes(prof.stop())
    lanes = lane_info["lanes"]
    result[f"{lane_prefix}device_blocked_seconds"] = \
        lanes["device_blocked"]
    result[f"{lane_prefix}host_dictionary_seconds"] = \
        lanes["host_dictionary"]
    result[f"{lane_prefix}compile_trace_lower_seconds"] = \
        lanes["compile_trace_lower"]
    result[f"{lane_prefix}attributed_fraction"] = \
        lane_info["attributed_fraction"]
    warm = min(timed(df) for _ in range(max(runs - 1, 1)))
    result[f"{name}_first_seconds"] = round(first, 4)
    result[f"{name}_warm_seconds"] = round(warm, 4)


def instrument_q1(data_dir: str, runs: int):
    """Per-stage decomposition of q1 + an AOT-compiled kernel measurement.

    Stages: parse (native .tbl scan -> numpy), h2d (host->device
    transfer), kernel (the engine's OWN partial-aggregation program —
    HashAggregateExec._get_grouped_fn — over the device-resident table,
    AOT-compiled and XLA cost-analyzed for flops/bytes so an estimated
    MFU rides along on the chip), so one on-chip run yields a full
    decomposition vs BASELINE.md.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ballista_tpu import col, count, sum_
    from ballista_tpu.columnar import ColumnBatch, round_capacity
    from ballista_tpu.io import TblSource
    from ballista_tpu.physical.aggregate import HashAggregateExec
    from ballista_tpu.physical.base import PhysicalPlan
    from benchmarks.tpch.schema_def import TPCH_SCHEMAS

    out: dict = {}
    schema = TPCH_SCHEMAS["lineitem"]
    src = TblSource(os.path.join(data_dir, "lineitem"), schema)
    names = ["l_returnflag", "l_linestatus", "l_quantity",
             "l_extendedprice", "l_discount", "l_tax", "l_shipdate"]
    sub = schema.project(names)

    # -- stage: parse (file -> numpy physical arrays, native C++ scanner) --
    t0 = time.time()
    n_total, arrays, dicts, valids = 0, None, {}, {}
    for p in range(src.num_partitions()):
        if src._use_native():
            n, arrs, ds, vs = src._scan_native(p, names)
        else:
            n, arrs, ds, vs = src._scan_pandas(p, names)
        if arrays is None:
            arrays, dicts, valids = arrs, ds, dict(vs or {})
            n_total = n
        else:  # multi-partition: host concat (parse-stage cost)
            # validity masks default to all-true when a chunk lacks one
            for k in set(valids) | set(vs or {}):
                left = valids.get(k, np.ones(n_total, dtype=bool))
                right = (vs or {}).get(k, np.ones(n, dtype=bool))
                valids[k] = np.concatenate([left, right])
            arrays = {k: np.concatenate([arrays[k], arrs[k]])
                      for k in arrays}
            n_total += n
    parse_s = time.time() - t0
    in_bytes = sum(a.nbytes for a in arrays.values())
    out["parse_s"] = round(parse_s, 4)
    out["parse_mb_per_s"] = round(in_bytes / parse_s / 1e6, 1)

    # -- stage: h2d (host numpy -> device buffers) --------------------------
    t0 = time.time()
    cap = round_capacity(n_total)
    batch = ColumnBatch.from_numpy(sub, arrays, dicts, capacity=cap,
                                   validity=valids or None)
    jax.block_until_ready([c.values for c in batch.columns])
    h2d_s = time.time() - t0
    out["h2d_s"] = round(h2d_s, 4)
    out["h2d_gb_per_s"] = round(in_bytes / h2d_s / 1e9, 2)
    out["rows"] = n_total

    # -- stage: kernel (the engine's q1 partial aggregation, AOT) ----------
    class _Stub(PhysicalPlan):
        def output_schema(self):
            return sub

        def with_new_children(self, children):
            return self

    from ballista_tpu import lit
    from ballista_tpu import expr as ex

    cutoff = ex.parse_date_literal("1998-09-02")
    pred = col("l_shipdate") <= ex.Literal(cutoff, sub.field("l_shipdate").dtype)
    disc_price = col("l_extendedprice") * (lit(1) - col("l_discount"))
    charge = disc_price * (lit(1) + col("l_tax"))
    aggs = [
        sum_(col("l_quantity")).alias("sum_qty"),
        sum_(col("l_extendedprice")).alias("sum_base_price"),
        sum_(disc_price).alias("sum_disc_price"),
        sum_(charge).alias("sum_charge"),
        sum_(col("l_discount")).alias("sum_disc"),
        count().alias("count_order"),
    ]
    partial = HashAggregateExec(
        "partial", [col("l_returnflag"), col("l_linestatus")], aggs,
        _Stub(), group_capacity=8,
    )
    from ballista_tpu.kernels.expr_eval import Evaluator

    ev = Evaluator(sub)

    def q1_program(b):
        live = jnp.logical_and(b.selection, ev.evaluate_predicate(pred, b))
        return partial._get_grouped_fn(8, cap)(b.with_selection(live))

    jitted = jax.jit(q1_program)
    t0 = time.time()
    lowered = jitted.lower(batch)
    compiled = lowered.compile()
    out["kernel_aot_compile_s"] = round(time.time() - t0, 3)
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0))
    bytes_accessed = float(ca.get("bytes accessed", 0.0))

    def run_kernel():
        t = time.time()
        jax.block_until_ready(compiled(batch))
        return time.time() - t

    run_kernel()  # warm any lazy allocs
    kernel_s = min(run_kernel() for _ in range(max(runs, 2)))
    out["kernel_s"] = round(kernel_s, 4)
    out["kernel_rows_per_s"] = round(n_total / kernel_s, 1)
    dev = jax.devices()[0]
    if flops:
        out["kernel_flops"] = flops
        out["kernel_bytes_accessed"] = bytes_accessed
        out["kernel_flops_per_s"] = round(flops / kernel_s, 1)
        if dev.platform != "cpu":
            peak = _peak_flops(dev.device_kind)
            out["est_mfu"] = round(flops / kernel_s / peak, 6)
            out["peak_flops_assumed"] = peak
        if bytes_accessed:
            out["kernel_gb_per_s"] = round(
                bytes_accessed / kernel_s / 1e9, 2)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--data", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_data"))
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend (not a device measurement)")
    _run_bench(ap.parse_args())


def _run_bench(args) -> None:
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    # overlap scan-chain XLA compiles with parse/H2D on the cold path
    # (compile/prewarm.py; an explicit user setting wins)
    os.environ.setdefault("BALLISTA_PREWARM", "1")
    import jax

    import ballista_tpu

    # persist fused-stage programs beside the XLA compile cache — where
    # JAX_COMPILATION_CACHE_DIR says, else the checkout's one fixed path
    # — so the first round exports them and every later fresh-process
    # round loads instead of re-tracing (compile/aot.py; an explicit
    # user setting wins)
    os.environ.setdefault(
        "BALLISTA_FUSION_AOT_DIR",
        os.path.join(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                     or ballista_tpu.XLA_CACHE_DIR, "fusion_aot"))
    dev0 = jax.devices()[0]
    platform = dev0.platform
    if platform == "cpu" and not args.cpu:
        print("bench.py: JAX found no accelerator and --cpu was not given; "
              "this benchmark does not fall back", file=sys.stderr)
        raise SystemExit(1)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from benchmarks.tpch import datagen
    from benchmarks.tpch.schema_def import TPCH_SCHEMAS, TPCH_PKS
    from ballista_tpu.client import BallistaContext

    # -- data ---------------------------------------------------------------
    data_dir = os.path.join(args.data, f"sf{args.scale:g}")
    marker = os.path.join(data_dir, ".complete")
    want = f"v{datagen.DATAGEN_VERSION}"
    have = open(marker).read().strip() if os.path.exists(marker) else None
    if have != want:
        if have is not None:
            print(f"# datagen version changed ({have} -> {want}): "
                  f"regenerating sf{args.scale:g}", file=sys.stderr)
        t0 = time.time()
        datagen.generate(data_dir, scale=args.scale, num_parts=1)
        open(marker, "w").write(want)
        print(f"# generated sf{args.scale:g} in {time.time()-t0:.1f}s",
              file=sys.stderr)

    sql = open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "benchmarks", "tpch", "queries", "q1.sql")).read()

    def run_once(ctx):
        t0 = time.time()
        out = ctx.sql(sql).collect()
        return time.time() - t0, out

    result = {
        "metric": "tpch_q1_rows_per_sec_warm", "value": 0,
        "unit": "rows/s", "vs_baseline": 0.0, "platform": platform,
        "device_kind": dev0.device_kind,
        "device_count": len(jax.devices()), "scale": args.scale,
    }

    from ballista_tpu.compile import compile_stats

    def record_compiles():
        # cold-path trajectory: process-wide XLA compile work and how
        # much of it the persistent disk cache absorbed (ISSUE 3 asks
        # for these in every bench line from this PR on)
        st = compile_stats()
        result["compile_count"] = int(st["backend_compiles"])
        result["compile_seconds"] = round(float(st["compile_seconds"]), 3)
        result["persistent_cache_hit"] = int(st["persistent_cache_hits"])
        # jit_programs = distinct governed entries minted this process
        # (ISSUE 6 tracks the whole-stage-fusion trajectory on this
        # field); per-specialization compile/retrieval events ride
        # alongside as compile_count / persistent_cache_hit, and
        # aot_loads counts whole programs deserialized WITHOUT tracing
        # (jit_trace_seconds pins the GIL-bound trace/lower mass those
        # loads eliminate)
        result["jit_programs"] = int(st.get("entries_built", 0))
        result["jit_trace_seconds"] = round(float(
            st.get("trace_seconds", 0.0)), 3)
        result["aot_loads"] = int(st.get("aot_loads", 0))
        # memory trajectory (ISSUE 5): BENCH_*.json records peak RSS
        # and peak device bytes alongside latency from this PR on
        from ballista_tpu.observability import memory as obs_memory

        result["peak_rss_mb"] = round(obs_memory.peak_rss_bytes() / 1e6, 1)
        result["peak_device_bytes"] = int(
            obs_memory.peak_device_bytes(refresh=True))
        result["peak_host_tracked_bytes"] = int(
            obs_memory.peak_host_bytes())
        # shuffle memory governor (ISSUE 12): in-flight peak + spill
        # volume per JSON line; the fixed-budget q5 phase below resets
        # and re-reads them for its gated fields
        from ballista_tpu.distributed import spill as _spill

        gov = _spill.governor().stats()
        result["spill_bytes"] = int(gov["spilled_bytes_total"])
        result["shuffle_peak_inflight_mb"] = round(
            gov["peak_inflight_bytes"] / 1e6, 2)
        # warm-path serving caches (docs/caching.md): scans served
        # device-resident, collects served from the result cache, and
        # governed calls that donated their input buffers — per JSON
        # line so dev/check_bench_regress.py can gate aliveness
        from ballista_tpu.cache import cache_counters

        cc = cache_counters()
        result["table_cache_hits"] = int(cc["table_cache_hits"])
        result["result_cache_hits"] = int(cc["result_cache_hits"])
        result["donated_buffers"] = int(cc["donated_buffers"])

    # -- cold: re-scan per run (what the reference benchmark does) ----------
    ctx_cold = BallistaContext.standalone()
    ctx_cold.register_tbl("lineitem", os.path.join(data_dir, "lineitem"),
                          TPCH_SCHEMAS["lineitem"],
                          primary_key=TPCH_PKS["lineitem"])
    # first run with parse/H2D/execute attribution (cold-path trajectory:
    # joins compile_count below; ISSUE 4 asks for these per JSON line)
    (cold_warmup, out), cold_phases = cold_phase_split(
        lambda: run_once(ctx_cold))
    result.update({
        "parse_seconds": cold_phases["parse_seconds"],
        "h2d_seconds": cold_phases["h2d_seconds"],
        "execute_seconds": cold_phases["execute_seconds"],
    })
    cold_s, _ = run_once(ctx_cold)
    total_rows = _count_lineitem_rows(data_dir)
    result.update({
        "lineitem_rows": total_rows,
        "cold_seconds": round(cold_s, 4),
        "cold_rows_per_sec": round(total_rows / cold_s, 1),
        "cold_vs_baseline": round(total_rows / cold_s / REF_ROWS_PER_SEC, 3),
        "first_run_seconds": round(cold_warmup, 4),
        "q1_groups": int(len(out)),
    })

    # -- warm: device-resident cached table + prepared (pre-compiled) query -
    from benchmarks.tpch.schema_def import register_tpch

    # On an accelerator, fewer/bigger batches amortize per-dispatch and
    # per-sync round-trips (decisive when the chip is remote); CPU keeps
    # the default where padding waste costs more than dispatches.
    reg_kw = {"batch_capacity": 1 << 23} if platform != "cpu" else {}
    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl", cached=True, **reg_kw)
    df = ctx.sql(sql)
    df.collect()  # load + compile once

    def timed(frame):
        t0 = time.time()
        frame.collect()
        return time.time() - t0

    warm = min(timed(df) for _ in range(args.runs))
    value = total_rows / warm
    result.update({
        "value": round(value, 1),
        "vs_baseline": round(value / REF_ROWS_PER_SEC, 3),
        "warm_seconds": round(warm, 4),
    })

    # -- q5 (join + shuffle-shaped query; BASELINE metric is q1+q5) ---------
    # The first q5 run executes under a profiler window so the named
    # wall-time lanes land in the JSON line: ROADMAP targets cite them
    # (item 2 wants host_dictionary < 0.5s) and
    # dev/check_bench_regress.py gates them between rounds. q5 keeps
    # the unprefixed legacy lane field names.
    qdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "tpch", "queries")
    profiled_query(ctx, "q5", open(os.path.join(qdir, "q5.sql")).read(),
                   args.runs, result, timed, lane_prefix="",
                   progress_field="progress_samples")
    if "q5_warm_seconds" in result:
        result["q5_rows_per_sec"] = round(
            total_rows / result["q5_warm_seconds"], 1)

    # -- q3 / q18 (ROADMAP item 5: grow bench coverage beyond
    # q1/q5/q12/q16 so the caches and AQE rules see diverse plan shapes
    # — q3 is join-heavy with a top-k sort, q18 a high-cardinality
    # aggregation feeding a join). Same lane/phase fields as q5,
    # prefixed per query; dev/check_bench_regress.py gates them.
    for qname in ("q3", "q18"):
        profiled_query(ctx, qname,
                       open(os.path.join(qdir, f"{qname}.sql")).read(),
                       args.runs, result, timed, lane_prefix=f"{qname}_")

    # -- q16 (COUNT(DISTINCT) query; the fused distinct-count kernel's
    # pinned workload — ISSUE 6 targets >=2x its r05 warm time). It is
    # also the bench's string-heavy JOIN query (partsupp joins part
    # under brand/type string predicates, groups by three string
    # columns, and anti-joins a comment LIKE subquery), so per ISSUE 11
    # / ROADMAP item 1 its first run emits the q16_-prefixed profiler
    # lane fields — q16_host_dictionary_seconds pins the lane the
    # dictionary registry exists to kill, gated between rounds by
    # dev/check_bench_regress.py.
    profiled_query(ctx, "q16", open(os.path.join(qdir, "q16.sql")).read(),
                   args.runs, result, timed, lane_prefix="q16_")

    # -- warm-path serving caches (docs/caching.md): repeated-query
    # warm phase (table-cache repeat scan + result-cache repeat
    # collect, byte-identity checked) and a fixed-budget residency
    # phase (budget sized below two tables, so the second fill EVICTS
    # the first and a re-scan degrades to re-ingest — never fails).
    # Gated by dev/check_bench_regress.py: the identity/ok fields are
    # aliveness gates, the warm latencies ride the ratio gates.
    _cache_phase(data_dir, result, sql, qdir)

    # -- fixed-budget spill q5 (ISSUE 12: memory-governed streaming
    # shuffle). q5 on an in-process LocalCluster with remote fetches
    # forced and a small BALLISTA_SHUFFLE_MEM_BUDGET: every shuffle
    # read streams through the governor and past-watermark chunks
    # spill to disk. Gated by dev/check_bench_regress.py — spill_bytes
    # must stay nonzero (the lane engaged) and the in-flight peak must
    # respect the budget (absolute budget_check).
    _spill_q5(data_dir, result, qdir)

    # -- per-stage decomposition + AOT kernel + MFU estimate ----------------
    result["stages"] = instrument_q1(data_dir, args.runs)

    # -- Pallas A/B on real accelerators ------------------------------------
    # The default dense path is XLA (measured faster for q1's tiny group
    # counts — see kernels/aggregate.py); re-run q1 with the Pallas
    # kernel forced ON so the delta is recorded automatically each run
    # and a future shape class that favors the kernel shows up in the
    # JSON. A FRESH context is required: operator jit caches bake the
    # path at trace time.
    if platform != "cpu":
        os.environ["BALLISTA_PALLAS"] = "on"
        try:
            ctx_p = BallistaContext.standalone()
            register_tpch(ctx_p, data_dir, "tbl", cached=True, **reg_kw)
            dfp = ctx_p.sql(sql)
            dfp.collect()  # load + compile with the Pallas path
            q1_pallas = min(timed(dfp) for _ in range(args.runs))
        finally:
            os.environ.pop("BALLISTA_PALLAS", None)
        result["q1_pallas_warm_seconds"] = round(q1_pallas, 4)
        result["q1_pallas_rows_per_sec"] = round(total_rows / q1_pallas, 1)
        result["pallas_vs_default"] = round(warm / q1_pallas, 3)
    record_compiles()
    print(json.dumps(result), flush=True)


def _cache_phase(data_dir: str, result: dict, sql: str,
                 qdir: str) -> None:
    """Warm-path serving caches (docs/caching.md), three measured
    legs on a FRESH residency tier so earlier phases' fills don't
    pollute the numbers:

    - repeat-scan q1: cold run fills the device table cache, warm run
      scans from pinned batches (parse + H2D ~ 0), byte-identity
      checked;
    - repeat-collect q1 with the result cache opted in: the second
      collect returns host-cached rows without executing;
    - fixed-budget leg: budget sized so lineitem fits but lineitem +
      orders does NOT — the orders fill evicts the coldest entry, the
      q1 re-scan degrades to re-ingest, results stay identical and the
      governed peak respects the budget."""
    from benchmarks.tpch.schema_def import TPCH_PKS, TPCH_SCHEMAS
    from ballista_tpu.cache import cache_counters, reset_cache_stats
    from ballista_tpu.cache import residency
    from ballista_tpu.client import BallistaContext

    def fresh_ctx(settings=None, tables=("lineitem",)):
        ctx = BallistaContext("standalone", settings=settings)
        for t in tables:
            ctx.register_tbl(t, os.path.join(data_dir, t),
                             TPCH_SCHEMAS[t], primary_key=TPCH_PKS[t])
        return ctx

    # -- tier (a): repeat-scan ---------------------------------------------
    residency._reset_for_tests()
    reset_cache_stats()
    df = fresh_ctx().sql(sql)
    t0 = time.time()
    base = df.collect()
    cold = time.time() - t0
    t0 = time.time()
    warm_out = df.collect()
    warm = time.time() - t0
    fill_bytes = int(cache_counters()["table_cache_resident_bytes"])
    result["cache_cold_q1_seconds"] = round(cold, 4)
    result["cache_warm_q1_seconds"] = round(warm, 4)
    result["cache_q1_speedup"] = round(cold / warm, 2) if warm > 0 else 0.0
    result["cache_q1_identical"] = int(base.equals(warm_out))
    result["table_cache_fill_bytes"] = fill_bytes

    # -- tier (c): repeat-collect (opt-in per context) -----------------------
    df_rc = fresh_ctx({"result_cache.enabled": "on"}).sql(sql)
    df_rc.collect()  # miss + fill (scans serve from the table cache)
    t0 = time.time()
    hit = df_rc.collect()
    rc = time.time() - t0
    result["result_cache_hit_seconds"] = round(rc, 4)
    result["result_cache_speedup"] = round(warm / rc, 1) if rc > 0 else 0.0
    result["result_cache_identical"] = int(base.equals(hit))

    # -- fixed-budget leg ----------------------------------------------------
    # the smallest whole-MB budget whose watermark still admits
    # lineitem: q1 pins it and the peak must respect the budget. Then
    # the budget is SHRUNK to 1 MB mid-leg (the knobs read the env at
    # call time, so an operator can tighten a live process): the orders
    # fill can only charge by evicting lineitem, and the q1 re-scan no
    # longer fits — it degrades to the plain streaming re-ingest.
    # Results stay byte-identical throughout; nothing ever fails.
    budget_mb = max(1, -(-fill_bytes // int(0.9 * (1 << 20))))
    residency._reset_for_tests()
    saved = os.environ.get("BALLISTA_TABLE_CACHE_BUDGET_MB")
    os.environ["BALLISTA_TABLE_CACHE_BUDGET_MB"] = str(budget_mb)
    try:
        ctx_b = fresh_ctx(tables=("lineitem", "orders"))
        dfb = ctx_b.sql(sql)
        out1 = dfb.collect()  # fills lineitem under the sized budget
        os.environ["BALLISTA_TABLE_CACHE_BUDGET_MB"] = "1"
        ctx_b.sql("SELECT COUNT(*) AS n FROM orders").collect()  # evicts
        out2 = dfb.collect()  # no longer fits: degrade to re-ingest
        cc = cache_counters()
        result["cache_budget_mb"] = budget_mb
        result["cache_budget_peak_resident_bytes"] = int(
            cc["table_cache_peak_resident_bytes"])
        result["cache_budget_ok"] = int(
            cc["table_cache_peak_resident_bytes"] <= budget_mb << 20)
        result["cache_budget_evictions"] = int(
            cc["table_cache_evictions"])
        result["cache_budget_identical"] = int(
            base.equals(out1) and base.equals(out2))
    finally:
        if saved is None:
            os.environ.pop("BALLISTA_TABLE_CACHE_BUDGET_MB", None)
        else:
            os.environ["BALLISTA_TABLE_CACHE_BUDGET_MB"] = saved
        residency._reset_for_tests()


def _spill_q5(data_dir: str, result: dict, qdir: str) -> None:
    """Fixed-budget q5 on an in-process LocalCluster: remote fetches
    forced so every shuffle read streams through the governed data
    plane, with a budget small enough that past-watermark chunks spill
    to size-rotated disk files. Emits the gated fields: wall time,
    spill volume, in-flight peak and the configured budget."""
    from benchmarks.tpch.schema_def import register_tpch
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.distributed import spill as _spill
    from ballista_tpu.distributed.executor import LocalCluster
    from ballista_tpu.observability import memory as obs_memory
    from ballista_tpu.physical.shuffle import ShuffleReaderExec

    # 128 KiB budget / 32 KiB chunks: in-flight wire bytes are bounded
    # by parts concurrently in fetch+decode (each part's buffer drains
    # at decode), so the budget must sit BELOW one part's wire volume
    # to genuinely force the spill lane at bench scales (>= 0.1)
    budget = 128 << 10
    chunk = 32 << 10
    saved = {k: os.environ.get(k) for k in
             ("BALLISTA_SHUFFLE_MEM_BUDGET", "BALLISTA_SHUFFLE_CHUNK_BYTES")}
    os.environ["BALLISTA_SHUFFLE_MEM_BUDGET"] = str(budget)
    os.environ["BALLISTA_SHUFFLE_CHUNK_BYTES"] = str(chunk)
    force_remote0 = ShuffleReaderExec.FORCE_REMOTE
    ShuffleReaderExec.FORCE_REMOTE = True
    gov = _spill.governor()
    gov.reset_stats()
    rss0 = obs_memory.peak_rss_bytes()
    cluster = LocalCluster(num_executors=2, concurrent_tasks=2)
    try:
        ctx = BallistaContext.remote("localhost", cluster.port,
                                     **{"job.timeout": "600"})
        register_tpch(ctx, data_dir, "tbl")
        sql = open(os.path.join(qdir, "q5.sql")).read()
        t0 = time.time()
        ctx.sql(sql).collect()
        wall = time.time() - t0
    finally:
        cluster.shutdown()
        ShuffleReaderExec.FORCE_REMOTE = force_remote0
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    st = gov.stats()
    result["spill_q5_seconds"] = round(wall, 4)
    result["spill_bytes"] = int(st["spilled_bytes_total"])
    result["shuffle_peak_inflight_mb"] = round(
        st["peak_inflight_bytes"] / 1e6, 2)
    result["spill_budget_mb"] = round(budget / 1e6, 2)
    result["spill_chunk_mb"] = round(chunk / 1e6, 2)
    result["spill_q5_peak_rss_mb"] = round(
        max(obs_memory.peak_rss_bytes(), rss0) / 1e6, 1)


def _count_lineitem_rows(data_dir: str) -> int:
    total = 0
    d = os.path.join(data_dir, "lineitem")
    for f in os.listdir(d):
        if f.endswith(".tbl"):
            with open(os.path.join(d, f), "rb") as fh:
                total += sum(buf.count(b"\n") for buf in
                             iter(lambda: fh.read(1 << 20), b""))
    return total


if __name__ == "__main__":
    main()
