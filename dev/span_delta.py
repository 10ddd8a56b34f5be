"""What one warm query of a benchmark cell costs the host, in the program's
own words: count and seconds by ``tracing.span_totals()`` key (launches by
program, blocked reads by site, hand-outs, the join and compaction events)
and the governed calls beside them, a mean over ``--rounds`` warm rounds.

    python dev/span_delta.py --workload standalone-join --seed 7 [--rounds 3]

Runs the cell through ``perfbench/engine.py`` as ``perfbench/run.py`` does
(same data, same warm-up rule); ``--rehearse`` is the CPU run at the
configuration's ``rehearse_scale``. One JSON line a query, keys by seconds,
then by count. Served cells sum over the executors' task threads, and say
under ``shuffle_write`` what the query's shuffling tasks handed to the Arrow
encoder (the ``shuffle.write`` events' ``fan_out``, ``batches`` and
``slices``, summed over ``tasks`` by the stages' ``ShuffleWrite`` rows).
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import run

    cell = run.find_cell(args.workload)
    os.environ.update(cell["config"].get("environment", {}))
    import engine
    run.load_reader("shuffle_fanout")  # puts perfbench/metrics on the path
    import _shuffle_write
    from ballista_tpu.compile import compile_stats
    from ballista_tpu.observability.tracing import span_totals

    def snap() -> dict:
        calls = {"count": compile_stats()["governed_calls"], "seconds": 0.0}
        return {**span_totals(), "governed_calls": calls}

    data_dir, tables, _, _ = run.cell_data(cell, args.seed, args.rehearse)
    eng = engine.Engine(cell["config"], data_dir, tables)
    sums, zero = {}, {"count": 0, "seconds": 0.0}  # query -> key -> [n, s]
    wrote = {}  # query -> the ShuffleWrite rows' counters, summed
    try:
        stream = run.Stream(0, eng.context(), cell, args.seed, None)
        run.warm_up([stream], int(cell["config"]["warm_rounds_max"]))
        for _ in range(args.rounds):
            for q in next(stream.rounds):
                before, t0 = snap(), time.time()
                stream.ctx.sql(cell["queries"][q]["text"]).collect()
                took = {"count": 1, "seconds": time.time() - t0}
                metrics = stream.ctx.last_query_metrics()
                got = _shuffle_write.of_stages(
                    dict(metrics.stages) if metrics is not None else {})
                for k, v in (got or {}).items():
                    acc = wrote.setdefault(q, {})
                    acc[k] = acc.get(k, 0) + v
                for key, t in {"query": took, **snap()}.items():
                    was = before.get(key, zero)
                    if t["count"] > was["count"]:
                        acc = sums.setdefault(q, {}).setdefault(key, [0, 0.0])
                        acc[0] += t["count"] - was["count"]
                        acc[1] += t["seconds"] - was["seconds"]
    finally:
        eng.close()
    for q, keys in sums.items():
        n = keys["query"][0]
        rows = sorted(keys.items(), key=lambda kv: (-kv[1][1], -kv[1][0]))
        line = {"query": q, "rounds": n, "a_query": {
            key: [c / n, round(s / n, 6)] for key, (c, s) in rows}}
        if wrote.get(q):
            line["shuffle_write"] = {k: v / n for k, v in wrote[q].items()}
        print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
