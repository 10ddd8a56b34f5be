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
(``join.build``, ``repart.materialize``, ``adaptive.rule``), then the plans
as text.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the ring's records of what a plan builds once (PR 41), less their ids
BUILT = ("adaptive.rule", "join.build", "repart.materialize")
DROPPED = ("ts", "pid", "tid", "sid", "psid")
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
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

    data_dir, tables, _, _ = run.cell_data(cell, args.seed, args.rehearse)
    eng = engine.Engine(cell["config"], data_dir, tables)
    plans = {}  # query -> [(execution, plan text)]
    try:
        stream = run.Stream(0, eng.context(), cell, args.seed, None)
        ctx, quiet, rounds = stream.ctx, 0, 0
        while rounds < int(cell["config"]["warm_rounds_max"]) and quiet < 2:
            before = engine.counters()["backend_compiles"]
            rounds += 1
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
                for r in ring_records():
                    if r.get("name") in BUILT:
                        print(json.dumps({k: v for k, v in r.items()
                                          if k not in DROPPED}), flush=True)
            added = engine.counters()["backend_compiles"] - before
            quiet = quiet + 1 if added == 0 else 0
            print(json.dumps({"round": rounds, "compiles": added}),
                  flush=True)
    finally:
        eng.close()
    for q, kept in plans.items():
        for execution, text in kept:
            print(f"== {q}, execution {execution} ==\n{text}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
