"""Per-operator wall-time profiler for a standalone query.

Wraps every physical operator's execute() so each yielded batch
attributes the time spent producing it (enqueue + any host sync) to the
yielding operator. Device work is async, so time shows up wherever a
host sync blocks — exactly what we want to find.

Usage: python dev/profile_query.py [--query q5] [--data benchmarks/bench_data/sf1]
"""

from __future__ import annotations

import argparse
import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--query", default="q5")
    ap.add_argument("--data", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_data", "sf1"))
    ap.add_argument("--runs", type=int, default=2)
    ap.add_argument("--sql", default=None,
                    help="profile this SQL string instead of --query")
    args = ap.parse_args()

    import jax

    from benchmarks.tpch.schema_def import register_tpch
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.physical.base import PhysicalPlan

    print(f"# platform: {jax.devices()[0].platform}", file=sys.stderr)

    ctx = BallistaContext.standalone()
    register_tpch(ctx, args.data, "tbl", cached=True)
    sql = args.sql or open(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "tpch", "queries", f"{args.query}.sql")).read()
    df = ctx.sql(sql)

    # one cold run to compile + warm caches
    t0 = time.perf_counter()
    df.collect()
    print(f"# cold: {time.perf_counter()-t0:.3f}s", file=sys.stderr)

    # instrument: wrap execute on the cached physical plan's nodes
    stats = collections.defaultdict(lambda: [0.0, 0])  # label -> [sec, batches]

    def wrap(node, seen):
        if id(node) in seen:
            return
        seen.add(id(node))
        label = node.display().split("\n")[0][:72]
        orig = node.execute

        def timed_execute(partition, _orig=orig, _label=label):
            it = _orig(partition)
            while True:
                t0 = time.perf_counter()
                try:
                    b = next(it)
                except StopIteration:
                    stats[_label][0] += time.perf_counter() - t0
                    return
                stats[_label][0] += time.perf_counter() - t0
                stats[_label][1] += 1
                yield b

        node.execute = timed_execute
        for c in node.children():
            wrap(c, seen)

    phys = getattr(df, "_phys", None)
    if phys is None:
        print("no cached physical plan (_phys); aborting", file=sys.stderr)
        sys.exit(1)
    wrap(phys, set())

    best = None
    for i in range(args.runs):
        for v in stats.values():
            v[0], v[1] = 0.0, 0
        t0 = time.perf_counter()
        df.collect()
        dt = time.perf_counter() - t0
        print(f"# run {i}: {dt:.3f}s", file=sys.stderr)
        if best is None or dt < best[0]:
            best = (dt, {k: tuple(v) for k, v in stats.items()})

    total, snap = best
    print(f"\n=== warm {args.query}: {total:.3f}s ===")
    acc = 0.0
    for label, (sec, nb) in sorted(snap.items(), key=lambda kv: -kv[1][0]):
        print(f"{sec:8.3f}s  {nb:5d} batches  {label}")
        acc += sec
    # note: parents include children's time (nested iteration), so the
    # sum exceeds wall; read top-down and compare levels
    print(f"# (nested totals; wall={total:.3f}s)")


if __name__ == "__main__":
    main()
