"""The plan a benchmark cell settles on, and what it keeps on the device:
each query's physical plan as the context caches it, annotated with the
metrics of its FIRST execution and of a warm one, and the device's bytes in
use after every query of the warm-up (what the cached plans retain between
queries shows as bytes that stay).

    python dev/settled_plan.py --workload standalone-join-sf10 --seed 7

Runs the cell through ``perfbench/engine.py`` as ``perfbench/run.py`` does
(same data, same warm-up rule: whole rounds until two in a row compile
nothing); ``--rehearse`` is the CPU run at the configuration's
``rehearse_scale``. Standalone cells only: a served query's plan lives in
the scheduler. One JSON line a query executed, after the first round one a
join build made, a repartition's sources sorted and a plan rewritten
(``join.build``, ``repart.materialize``, ``adaptive.rule``), after the last
round what it gathered (``join.expand`` and ``repart.take`` events counted by
their columns and capacity), then the plans as text. ``--memory-trace``
samples the device's bytes in use every 20 ms through the first round and
prints, for each thing built, the most it saw while that span was open, and
the spans open at the round's highest sample: who owns the allocator's peak.
"""

import argparse
import json
import os
import sys
import threading
import time
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the ring's records of what a plan builds once (PR 41), less their ids
BUILT = ("adaptive.rule", "join.build", "repart.materialize")
# what a warm query gathers: slots by columns (PR 42)
GATHERED = {"join.expand": "to", "repart.take": "capacity"}
DROPPED = ("ts", "pid", "tid", "sid", "psid")
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--memory-trace", action="store_true")
    args = ap.parse_args()
    import run

    cell = run.find_cell(args.workload)
    if cell["config"]["mode"] != "standalone":
        raise SystemExit("settled_plan.py: standalone cells only")
    os.environ.update(cell["config"].get("environment", {}))
    import jax

    import engine
    from ballista_tpu.observability.tracing import ring_records

    def memory() -> dict:
        st = jax.devices()[0].memory_stats() or {}
        return {"in_use_gb": st.get("bytes_in_use", 0) / 1e9,
                "peak_gb": st.get("peak_bytes_in_use", 0) / 1e9}

    samples, sampling = [], threading.Event()  # (epoch, bytes in use)

    def sample() -> None:
        while sampling.is_set():
            st = jax.devices()[0].memory_stats() or {}
            samples.append((time.time(), st.get("bytes_in_use", 0)))
            time.sleep(0.02)

    def owners(built) -> None:
        """The highest sample inside each span of ``built``, then the spans
        open at the highest sample of all."""
        if not samples:
            return
        top_at, top = max(samples, key=lambda s: s[1])
        for r in built:
            t0, t1 = r["ts"], r["ts"] + r.get("dur", 0.0)
            seen = [b for t, b in samples if t0 <= t <= t1]
            print(json.dumps({
                "memory": r["name"], "side": r.get("side"),
                "rows": r.get("rows"), "from_s": t0 - samples[0][0],
                "dur": r.get("dur"), "max_in_use_gb": max(seen, default=0) / 1e9,
                "open_at_top": t0 <= top_at <= t1}), flush=True)
        opened = Counter(
            r["name"] for r in ring_records()
            if "dur" in r and r["ts"] <= top_at <= r["ts"] + r["dur"])
        halves = {}  # the most seen in each half second of the round
        for t, b in samples:
            at = int((t - samples[0][0]) * 2)
            halves[at] = max(halves.get(at, 0), b)
        print(json.dumps({"memory": "top", "at_s": top_at - samples[0][0],
                          "in_use_gb": top / 1e9, "samples": len(samples),
                          "open": dict(opened),
                          "gb_by_half_second": [
                              round(halves.get(i, 0) / 1e9, 2)
                              for i in range(max(halves) + 1)]}),
              flush=True)

    data_dir, tables, _, _ = run.cell_data(cell, args.seed, args.rehearse)
    eng = engine.Engine(cell["config"], data_dir, tables)
    plans = {}  # query -> [(execution, plan text)]
    try:
        stream = run.Stream(0, eng.context(), cell, args.seed, None)
        ctx, quiet, rounds = stream.ctx, 0, 0
        while rounds < int(cell["config"]["warm_rounds_max"]) and quiet < 2:
            before = engine.counters()["backend_compiles"]
            rounds += 1
            started = time.time()
            if args.memory_trace and rounds == 1:
                sampling.set()
                threading.Thread(target=sample, daemon=True).start()
            for q in next(stream.rounds):
                t0 = time.time()
                ctx.sql(cell["queries"][q]["text"]).collect()
                print(json.dumps({"round": rounds, "query": q,
                                  "seconds": time.time() - t0, **memory()}),
                      flush=True)
                text = ctx._last_query_phys.pretty_metrics()
                kept = plans.setdefault(q, [])
                kept[1:] = [(rounds, text)]  # the first and the latest
            if rounds == 1:  # what the first executions built and kept
                sampling.clear()
                built = [r for r in ring_records() if r.get("name") in BUILT]
                for r in built:
                    print(json.dumps({k: v for k, v in r.items()
                                      if k not in DROPPED}), flush=True)
                owners([r for r in built if "dur" in r])
            added = engine.counters()["backend_compiles"] - before
            quiet = quiet + 1 if added == 0 else 0
            print(json.dumps({"round": rounds, "compiles": added}),
                  flush=True)
        gathered = Counter(
            (r["name"], r.get("cols"), r[GATHERED[r["name"]]])
            for r in ring_records(since=started)
            if r.get("name") in GATHERED)
        for (name, cols, slots), n in sorted(gathered.items(), key=str):
            print(json.dumps({"round": rounds, "name": name, "cols": cols,
                              GATHERED[name]: slots, "count": n}),
                  flush=True)
    finally:
        eng.close()
    for q, kept in plans.items():
        for execution, text in kept:
            print(f"== {q}, execution {execution} ==\n{text}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
