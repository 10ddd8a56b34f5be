"""chip_smoke.py: the quickest proof that ballista-tpu still starts on the chip.

    python chip_smoke.py             # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4   # the four-chip mesh path, and only that

ONE process, which owns the chip from first to last, drives the system's
main path through the entry points a user would call, at TPC-H SF1
(6,000,699 lineitem rows at the default seed) made inside the command by
``benchmarks/tpch/datagen.generate(..., seed=<--seed>)``:

- **standalone**: ``BallistaContext.standalone()`` + ``register_tpch`` ->
  q1, q6 and one join query (``--queries``), each once cold and three
  times warm;
- **served**: in the same process a ``LocalCluster`` (2 executors x 2
  task slots, threads — still one process on the chip) behind
  ``BallistaContext.remote``: q1 and the join query through scheduler ->
  executor -> shuffle data plane -> client;
- ``--chips 4`` instead runs ONLY the mesh path: one executor driving
  four devices, one join query (q12) whose stage plans must hold a mesh
  operator, compared with the oracle and with the one-device plan's
  result, and every device must have held data.

Every result is compared with ``benchmarks/tpch/oracle.py`` on the same
data, outside the timed part. A mismatch, any exception, the pandas
reader or the Python data plane standing in for the native ones, or no
TPU, ends the run non-zero: nothing here catches and carries on. Earlier
stdout lines are one JSON object each — observations for whoever works
on the engine next (compile bill, syncs, H2D, memory), not claims. The
last line is the contract's:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` lets the same flow run where JAX has no TPU (the CPU
rehearsal before a chip call, tiny ``--scale``); its last line never
says ``"ok": true``.

Which join query: q3/q5 at SF1 hold 5 programs each with a ``lax.sort``
over >= 64K rows (join builds, hash repartition, sort-based aggregates),
and the TPU compiler takes tens of seconds to minutes for each — on the
chip a cold q3 compiled for 462 s standalone and 267 s more served
(PERF.md, PR 22), more than this script's time limit beside datagen. q14 (lineitem JOIN part + CASE
aggregate) compiles no sort at all, so it is the join query here, and
q12 (orders JOIN lineitem, one 64K-row build sort, a mesh-fused final
aggregate) the four-chip one; ``--queries q1,q6,q3`` runs others by hand
with a longer limit. See PERF.md section 5 for the count.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
QDIR = os.path.join(HERE, "benchmarks", "tpch", "queries")

# tables each oracle query reads (pandas loads only these)
QUERY_TABLES = {
    "q1": {"lineitem"},
    "q6": {"lineitem"},
    "q14": {"lineitem", "part"},
    "q12": {"lineitem", "orders"},
    "q3": {"lineitem", "orders", "customer"},
    "q5": {"lineitem", "orders", "customer", "supplier", "nation",
           "region"},
}
MESH_OPERATORS = ("MeshJoinExec", "MeshAggExec")
# .tbl files per large table = scan partitions: enough for both served
# executors' slots, and one per device on the four-chip mesh
PARTS = 4


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def sql_of(query: str) -> str:
    with open(os.path.join(QDIR, f"{query}.sql")) as fh:
        return fh.read()


def read_counters() -> dict:
    """Process-wide counters the engine already keeps, as one flat dict."""
    from ballista_tpu.cache import cache_counters
    from ballista_tpu.compile import compile_stats
    from ballista_tpu.ingest import phase_bytes, phase_totals

    st = compile_stats()
    ph = phase_totals()
    cc = cache_counters()
    return {
        "backend_compiles": int(st["backend_compiles"]),
        "persistent_cache_hits": int(st["persistent_cache_hits"]),
        "compile_seconds": float(st["compile_seconds"]),
        "parse_seconds": float(ph["parse"]),
        "h2d_seconds": float(ph["h2d"]),
        "h2d_bytes": int(phase_bytes().get("h2d", 0)),
        "table_cache_hits": int(cc["table_cache_hits"]),
        "donated_buffers": int(cc["donated_buffers"]),
    }


def counters_delta(before: dict, after: dict) -> dict:
    """What the work between two ``read_counters()`` added."""
    out = {k: after[k] - before[k] for k in after}
    # jax reports a disk-cache hit as a (short) backend compile too
    out["compiled_fresh"] = (out["backend_compiles"]
                             - out["persistent_cache_hits"])
    return {k: round(v, 4) if isinstance(v, float) else v
            for k, v in out.items()}


def device_block_spans(since: float) -> int:
    """Blocking host<-device syncs since ``since``: the ``device.block``
    spans in the always-on flight recorder (bounded ring: a cold query
    that emits more records than the ring holds under-counts)."""
    from ballista_tpu.observability.tracing import ring_records

    return sum(1 for r in ring_records(since=since)
               if r.get("name") == "device.block")


def run_query(ctx, query: str, path: str, expected, warm_runs: int = 3):
    """One query through ``ctx``: once cold, ``warm_runs`` times warm,
    then (untimed) compared with the oracle. Returns the result frame."""
    from benchmarks.tpch.oracle import assert_frames_match

    sql = sql_of(query)
    c0 = read_counters()
    t0 = time.time()
    got = ctx.sql(sql).collect()
    cold = time.time() - t0
    cold_blocks = device_block_spans(t0)
    c1 = read_counters()
    warm, warm_blocks = [], 0
    for _ in range(warm_runs):
        t0 = time.time()
        again = ctx.sql(sql).collect()
        warm.append(time.time() - t0)
        warm_blocks = device_block_spans(t0)
    c2 = read_counters()
    assert_frames_match(query, got, expected)
    assert_frames_match(query, again, expected)
    emit({"phase": f"{path}.{query}", "cold_seconds": cold,
          "warm_seconds": warm, "rows_out": int(len(got)),
          "equals_oracle": True,
          "device_block_spans_cold": cold_blocks,
          "device_block_spans_last_warm": warm_blocks,
          "cold": counters_delta(c0, c1),
          "warm_total": counters_delta(c1, c2)})
    return got


def make_data(args) -> str:
    """TPC-H .tbl files from ``--seed`` inside the checkout (git-ignored
    ``bench_data/``); a finished directory of the same seed, scale and
    datagen version is reused (a second run in one chip call)."""
    from benchmarks.tpch import datagen

    data_dir = args.data or os.path.join(
        HERE, "bench_data",
        f"smoke_sf{args.scale:g}_seed{args.seed}_p{PARTS}")
    marker = os.path.join(data_dir, ".complete")
    want = f"v{datagen.DATAGEN_VERSION}"
    have = open(marker).read().strip() if os.path.exists(marker) else None
    t0 = time.time()
    if have != want:
        datagen.generate(data_dir, scale=args.scale, num_parts=PARTS,
                         seed=args.seed)
        with open(marker, "w") as fh:
            fh.write(want)
    emit({"phase": "setup.datagen", "scale": args.scale, "seed": args.seed,
          "parts": PARTS, "reused": have == want,
          "seconds": round(time.time() - t0, 1)})
    return data_dir


def make_oracle(data_dir: str, queries) -> dict:
    """Expected frames from the independent pandas implementation."""
    from benchmarks.tpch import oracle

    t0 = time.time()
    need = set().union(*(QUERY_TABLES[q] for q in queries))
    tables = oracle.load_tables(data_dir, only=need)
    expected = {q: oracle.ORACLES[q](tables) for q in queries}
    emit({"phase": "setup.oracle", "tables": sorted(need),
          "lineitem_rows": int(len(tables["lineitem"])),
          "seconds": round(time.time() - t0, 1)})
    return expected


def require_native_scanner() -> None:
    from ballista_tpu.io import native

    if not native.available():
        raise SystemExit("chip_smoke: the native .tbl scanner could not be "
                         "built or loaded; the pandas reader is not the "
                         "path under test")
    emit({"phase": "setup.scanner", "scanner": "native",
          "lib": os.path.relpath(native._LIB_PATH, HERE)})


def require_native_data_plane(cluster) -> None:
    from ballista_tpu.distributed.dataplane import NativeDataPlane

    planes = [type(e._data_plane).__name__ for e in cluster.executors]
    if not all(isinstance(e._data_plane, NativeDataPlane)
               for e in cluster.executors):
        raise SystemExit(f"chip_smoke: executors serve shuffle data with "
                         f"{planes}; the Python server is not the path "
                         "under test")
    emit({"phase": "setup.data_plane", "data_plane": "native",
          "executors": len(planes)})


def one_chip(args, data_dir: str, expected: dict) -> None:
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.distributed.executor import LocalCluster
    from benchmarks.tpch.schema_def import register_tpch

    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    for q in args.queries:
        run_query(ctx, q, "standalone", expected[q])

    cluster = LocalCluster(num_executors=2, concurrent_tasks=2,
                           num_devices=1)
    try:
        require_native_data_plane(cluster)
        rctx = BallistaContext.remote("localhost", cluster.port,
                                      **{"job.timeout": "1500"})
        register_tpch(rctx, data_dir, "tbl")
        # the aggregation query and the (last) join query, served
        for q in dict.fromkeys((args.queries[0], args.queries[-1])):
            run_query(rctx, q, "served", expected[q])
    finally:
        cluster.shutdown()


def stage_operators(cluster, job_id: str) -> dict:
    """{stage_id: [operator class names]} of the job's stage plans as the
    scheduler stored them (mesh-fused stages included)."""
    from ballista_tpu import serde
    from ballista_tpu.proto import ballista_pb2 as pb

    def names(plan):
        out = [type(plan).__name__]
        for c in plan.children():
            out.extend(names(c))
        return out

    stages = {}
    for (job, stage_id) in sorted(cluster.state._stage_parts):
        if job != job_id:
            continue
        row = cluster.state.get_stage_plan(job_id, stage_id)
        node = pb.PhysicalPlanNode()
        node.ParseFromString(row.plan_bytes)
        stages[stage_id] = names(serde.physical_from_proto(node))
    return stages


def device_peaks(devices):
    """Allocator peak per device (a device nothing has touched yet may
    report no stats: 0), or None where the platform keeps none (CPU)."""
    if devices[0].platform != "tpu":
        return None
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def four_chips(args, data_dir: str, expected: dict) -> None:
    """Only the mesh path and what it is compared with."""
    import jax

    from ballista_tpu.client import BallistaContext
    from ballista_tpu.distributed.executor import LocalCluster
    from benchmarks.tpch.oracle import assert_frames_match
    from benchmarks.tpch.schema_def import register_tpch

    query = args.queries[-1]
    devices = jax.devices()[:4]

    # the one-device plan's result: the same query standalone (device 0)
    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    single = run_query(ctx, query, "one_device_plan", expected[query],
                       warm_runs=1)
    peak0 = device_peaks(devices)

    cluster = LocalCluster(num_executors=1, concurrent_tasks=2,
                           num_devices=4)
    try:
        require_native_data_plane(cluster)
        rctx = BallistaContext.remote(
            "localhost", cluster.port,
            **{"job.timeout": "1500", "mesh.devices": "4",
               "agg.partitions": "4", "join.partitions": "4"})
        register_tpch(rctx, data_dir, "tbl")
        meshed = run_query(rctx, query, "mesh4", expected[query])
        stages = stage_operators(cluster, rctx._last_job_id)
    finally:
        cluster.shutdown()
    assert_frames_match(query, meshed, single)
    mesh_ops = sorted({op for ops in stages.values() for op in ops
                       if op in MESH_OPERATORS})
    if not mesh_ops:
        raise SystemExit(f"chip_smoke: no mesh operator in {query}'s "
                         f"stage plans: {stages}")
    emit({"phase": "mesh4.plan", "query": query, "mesh_operators": mesh_ops,
          "stages": {str(k): v for k, v in stages.items()},
          "equals_one_device_plan": True})

    # every device must have HELD data, not only device 0: the mesh phase
    # must have raised the allocator's peak on each of them (the CPU
    # devices of a rehearsal keep no allocator stats: not checkable)
    if peak0 is None:
        emit({"phase": "mesh4.devices", "checked": False,
              "reason": "no allocator stats on this platform"})
        return
    peak1 = device_peaks(devices)
    emit({"phase": "mesh4.devices", "checked": True,
          "peak_bytes_before_mesh": peak0, "peak_bytes_in_use": peak1})
    if not all(after > before for before, after in zip(peak0[1:], peak1[1:])):
        raise SystemExit(f"chip_smoke: devices 1..3 held no mesh data: "
                         f"{peak0} -> {peak1}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--queries", default=None,
                    help="comma list; the last one is the join query "
                         "(default q1,q6,q14; with --chips 4: q12)")
    ap.add_argument("--data", default=None,
                    help="data directory (default: bench_data/ in the "
                         "checkout)")
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU (CPU rehearsal); never ok")
    args = ap.parse_args()
    args.queries = (args.queries.split(",") if args.queries
                    else ["q12"] if args.chips == 4 else ["q1", "q6", "q14"])

    sys.path.insert(0, HERE)
    import ballista_tpu  # noqa: F401 - places the compile cache first
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (devices: {device}); this "
              "script does not fall back", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    emit({"phase": "start", "jax": jax.__version__,
          "jaxlib": importlib.metadata.version("jaxlib"),
          "libtpu": importlib.metadata.version("libtpu"),
          "device": device, "chips": args.chips, "queries": args.queries,
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "compile_cache_dir_from_env":
              bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))})

    t_start = time.time()
    require_native_scanner()
    data_dir = make_data(args)
    expected = make_oracle(data_dir, args.queries)
    if args.chips == 4:
        four_chips(args, data_dir, expected)
    else:
        one_chip(args, data_dir, expected)

    from ballista_tpu.compile import compile_stats
    from ballista_tpu.observability import memory as obs_memory

    st = compile_stats()
    peak = device_peaks(devices[:1])
    engine_peak = int(obs_memory.peak_device_bytes(refresh=True))
    # on the chip the engine's own sampler must read the allocator too,
    # not sum live arrays as it does on the CPU backend
    if peak is not None and engine_peak <= 0:
        raise SystemExit("chip_smoke: the engine's device-memory sampler "
                         "read nothing from the allocator")
    emit({"phase": "totals", "seconds": round(time.time() - t_start, 1),
          "peak_bytes_in_use": peak and peak[0],
          "engine_peak_device_bytes": engine_peak,
          "backend_compiles": int(st["backend_compiles"]),
          "compile_seconds": round(float(st["compile_seconds"]), 1),
          "compile_cache_dir": jax.config.jax_compilation_cache_dir,
          "compile_cache_hits": int(st["persistent_cache_hits"])})
    if device["platform"] == "tpu":
        emit({"ok": True, "device": device})
    else:
        emit({"ok": False, "rehearsal": "passed", "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
