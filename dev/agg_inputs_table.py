"""The chip table of how an aggregate's several input batches are put
together: q1's and q6's partial stage (``perfbench/queries``) over ONE
partition of the scan-aggregate cells' shape (SF3 in 4 files: 4 batches of
1<<20 rows and a tail rung of 1<<19), each way timed on its own.

    python dev/agg_inputs_table.py [--reps 40] [--rows 4499000]

Ways (``physical/aggregate.py`` ``_partition_input`` keeps ``in_program``):

- ``host_concat``: ``concat_batches`` eagerly (one jax launch a column),
  then the donating program over the fresh buffer: what ran before PR 40.
- ``in_program``: the program takes the tuple and concatenates in its trace.
- ``one_batch``: the program over a batch that was concatenated beforehand,
  outside the timing: the floor, what a copy-free assembly could reach.
- ``per_piece``: the one-batch program once a piece, five launches: what
  "the states of each piece added up" costs the device before the adding.

A row: wall ms a call (``--reps`` calls back to back, one blocking read at
the end), device ms a call and programs a call from a profiler trace of the
same loop (``XLA Modules`` line; "not measured" without a device plane), and
the seconds the first call took (compile or cache read). One JSON line a
row.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "perfbench"),
                os.path.join(ROOT, "dev")]


def _pieces(rows: int, seed: int):
    import numpy as np

    from ballista_tpu import Date32, Decimal, Utf8, schema
    from ballista_tpu.columnar import ColumnBatch, Dictionary

    s = schema(("l_quantity", Decimal(2)), ("l_extendedprice", Decimal(2)),
               ("l_discount", Decimal(2)), ("l_tax", Decimal(2)),
               ("l_returnflag", Utf8), ("l_linestatus", Utf8),
               ("l_shipdate", Date32))
    rng = np.random.default_rng(seed)
    table = {
        "l_quantity": rng.integers(1, 51, rows) * 100,
        "l_extendedprice": rng.integers(90_000, 10_500_000, rows),
        "l_discount": rng.integers(0, 11, rows),
        "l_tax": rng.integers(0, 9, rows),
        "l_returnflag": rng.integers(0, 3, rows).astype(np.int32),
        "l_linestatus": rng.integers(0, 2, rows).astype(np.int32),
        "l_shipdate": rng.integers(8036, 10562, rows).astype(np.int32),
    }
    dicts = {"l_returnflag": Dictionary(["A", "N", "R"]),
             "l_linestatus": Dictionary(["F", "O"])}
    out, lo = [], 0
    while lo < rows:
        hi = min(lo + (1 << 20), rows)
        out.append(ColumnBatch.from_numpy(
            s, {k: v[lo:hi] for k, v in table.items()}, dicts))
        lo = hi
    return s, out


def _partial_stage(schema, pieces, query: str):
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.execution import plan_logical
    from ballista_tpu.io import MemTableSource
    from ballista_tpu.physical.fusion import FusedStageExec, maybe_fuse
    from ballista_tpu.physical.planner import PlannerOptions

    ctx = BallistaContext.standalone()
    ctx.register_source("lineitem", MemTableSource(schema, [list(pieces)]))
    with open(os.path.join(ROOT, "perfbench", "queries", query + ".sql")) as fh:
        plan = ctx.sql(fh.read()).logical_plan()
    node = maybe_fuse(plan_logical(
        plan, PlannerOptions.from_settings(ctx.settings)))
    while not (isinstance(node, FusedStageExec) and node.mode == "partial"):
        node = node.children()[0]
    return node


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--rows", type=int, default=4_499_000)
    ap.add_argument("--seed", type=int, default=40)
    args = ap.parse_args()
    import jax

    import xplane
    from ballista_tpu.physical.base import concat_batches
    from trace_programs import programs as device_programs

    schema, pieces = _pieces(args.rows, args.seed)
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform,
                      "rows": args.rows,
                      "capacities": [p.capacity for p in pieces]}))
    for query in ("q1", "q6"):
        st = _partial_stage(schema, pieces, query)
        run = ((lambda inp: st._exec_grouped(inp, "")[0]) if st.group_exprs
               else st._exec_scalar)
        whole = concat_batches(schema, list(pieces))
        whole._transient = False  # kept across calls: never donated
        ways = {
            "host_concat": lambda: run(concat_batches(schema, list(pieces))),
            "in_program": lambda: run(tuple(pieces)),
            "one_batch": lambda: run(whole),
            "per_piece": lambda: [run(p) for p in pieces][-1],
        }
        answers = {}
        for way, call in ways.items():
            t0 = time.perf_counter()
            answers[way] = jax.block_until_ready(call())
            first_s = time.perf_counter() - t0
            jax.block_until_ready(call())
            t0 = time.perf_counter()
            for _ in range(args.reps):
                out = call()
            jax.block_until_ready(out)
            wall_ms = (time.perf_counter() - t0) / args.reps * 1e3
            trace_dir = tempfile.mkdtemp(prefix="agg_inputs_")
            jax.profiler.start_trace(
                trace_dir, profiler_options=xplane.profiler_options())
            try:
                for _ in range(args.reps):
                    out = call()
                jax.block_until_ready(out)
            finally:
                jax.profiler.stop_trace()
            programs = device_programs(xplane.load(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
            row = {"query": query, "way": way, "wall_ms": round(wall_ms, 4),
                   "first_call_s": round(first_s, 3)}
            if programs is None:
                row["device_ms"] = "not measured"
            else:
                row["device_ms"] = round(sum(
                    s for _, s in programs.values()) / args.reps * 1e3, 4)
                row["programs_a_call"] = {
                    n: [c / args.reps, round(s / c * 1e3, 4)]
                    for n, (c, s) in sorted(programs.items(),
                                            key=lambda kv: -kv[1][1])}
            print(json.dumps(row), flush=True)
        if query == "q1":  # the same groups' sums, whichever way
            want = answers["one_batch"].to_pandas()
            for way in ("host_concat", "in_program"):
                assert answers[way].to_pandas().equals(want), way
    return 0


if __name__ == "__main__":
    sys.exit(main())
