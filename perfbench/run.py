"""One run of one cell of the benchmark.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip from first to last: it finds the cell's
files by the names in ``BENCHMARK.json``, makes the data from ``--seed`` (or
finds it), starts the deployment the configuration describes, warms up until
the program has stopped compiling, measures whole rounds of the traffic for
``--seconds``, compares every answer of the window with the plain reference,
and prints the contract's one JSON line last. Without a TPU it exits non-zero
and prints no result; ``--rehearse`` runs the same flow on the CPU at the
configuration's ``rehearse_scale``, and its line says so.

Files of a cell (see README.md): ``configs/<config>.json`` (the ``file`` of
the configuration's entry), ``traffic/<traffic>.json``,
``cells/<workload>.json``, ``queries/<query>.json`` + ``.sql`` + ``.py``,
``tables/<table>.py``, ``metrics/<metric>.py``.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DATA_ROOT = os.path.join(ROOT, "perfbench_data")   # git-ignored
TRACE_ROOT = os.path.join(ROOT, "perfbench_trace")  # git-ignored
TRACE_SECONDS = 4.0  # a traced run profiles whole rounds for this long


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def find_cell(workload: str) -> dict:
    """The cell's entries and files, by the names in BENCHMARK.json."""
    bench = read_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    traffic = read_json(HERE, "traffic", cell["traffic"] + ".json")
    queries = {}
    for q in dict.fromkeys(traffic["round"]):
        spec = read_json(HERE, "queries", q + ".json")
        with open(os.path.join(HERE, "queries", spec["sql"])) as fh:
            spec["text"] = fh.read()
        queries[q] = spec
    return {"name": workload, "chips": int(cell["chips"]),
            "config": read_json(ROOT, entry["file"]), "traffic": traffic,
            "reports": read_json(HERE, "cells", workload + ".json"),
            "queries": queries}


def load_reader(name: str):
    """``metrics/<name>.py``."""
    import byname

    return byname.load("metrics", name)


def cell_data(cell: dict, seed: int, rehearse: bool) -> tuple:
    """(data directory, tables, tables made now, seconds) for the tables the cell's
    queries read, at the configuration's scale (``rehearse_scale`` in a
    rehearsal). The directory is keyed by scale, seed, generator version and
    files a table; a finished table in it is found, not made again."""
    import datagen

    config = cell["config"]
    scale = config["rehearse_scale"] if rehearse else config["scale"]
    files = int(config["files_per_table"])
    tables = sorted({t for spec in cell["queries"].values()
                     for t in spec["reads"]})
    data_dir = os.path.join(
        DATA_ROOT, f"sf{scale:g}_seed{seed}_v{datagen.DATAGEN_VERSION}"
                   f"_f{files}")
    missing = [t for t in tables if not os.path.exists(
        os.path.join(data_dir, t, ".complete"))]
    t0 = time.time()
    if missing:
        for t in missing:
            shutil.rmtree(os.path.join(data_dir, t), ignore_errors=True)
        datagen.generate(data_dir, scale, missing, files, seed)
        for t in missing:
            open(os.path.join(data_dir, t, ".complete"), "w").close()
    return data_dir, tables, missing, time.time() - t0


class Stream:
    """One closed-loop client: a context of its own and its rounds."""

    def __init__(self, index, ctx, cell, seed, after_query):
        """``after_query``: None, or in a traced run the readers' hooks of
        that name by reader; each query then gets a record."""
        import traffic

        self.index, self.ctx, self.cell = index, ctx, cell
        self.rounds = traffic.rounds(cell["traffic"], seed, index)
        self.after_query = after_query
        self.done = []  # (query, started, seconds, frame | None, record)

    def run_round(self) -> None:
        import jax

        import engine

        for q in next(self.rounds):
            text = self.cell["queries"][q]["text"]
            started = time.time()
            try:
                with jax.profiler.TraceAnnotation("collect:" + q):
                    frame = self.ctx.sql(text).collect()
            except Exception as exc:  # noqa: BLE001 - a failed query is counted, the run goes on
                print(f"run.py: {q} failed: {exc!r}", file=sys.stderr)
                frame = None
            seconds = time.time() - started
            record = None
            if self.after_query is not None and frame is not None:
                # the hooks first: taking the record can itself sync
                own = {name: hook(self.ctx, started, seconds)
                       for name, hook in self.after_query.items()}
                record = {**engine.query_record(self.ctx), "readers": own}
            self.done.append((q, started, seconds, frame, record))


def each_stream(streams, fn) -> None:
    """``fn(stream)`` on every stream, concurrently where there are several."""
    if len(streams) == 1:
        fn(streams[0])
        return
    errors = []

    def guarded(s):
        try:
            fn(s)
        except BaseException as exc:  # noqa: BLE001 - re-raised below
            errors.append(exc)

    threads = [threading.Thread(target=guarded, args=(s,)) for s in streams]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def warm_up(streams, max_rounds: int) -> dict:
    """Whole rounds until two in a row add no backend compile (a program
    read back from the disk cache counts as one)."""
    import engine

    cold, rounds, quiet = {}, 0, 0
    while rounds < max_rounds and quiet < 2:
        before = engine.counters()["backend_compiles"]
        first = len(streams[0].done)
        each_stream(streams, Stream.run_round)
        rounds += 1
        for q, _, seconds, frame, _ in streams[0].done[first:]:
            if frame is not None:
                cold.setdefault(q, seconds)
        added = engine.counters()["backend_compiles"] - before
        quiet = quiet + 1 if added == 0 else 0
        say({"phase": "warm_up", "round": rounds, "compiles": added})
    for s in streams:
        s.done.clear()
    return {"rounds": rounds, "settled": quiet >= 2, "cold_query_s": cold}


def measure(streams, seconds: float, trace_dir) -> dict:
    """Whole rounds until ``seconds`` have passed: the window ends at the
    first round boundary at or after it, on every stream. A traced run
    profiles stream 0's first rounds, up to TRACE_SECONDS."""
    import jax

    import xplane

    tracing = trace_dir is not None
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=xplane.profiler_options())
    t0 = time.time()
    ends = {}

    def loop(stream):
        nonlocal tracing
        while True:
            stream.run_round()
            now = time.time()
            if stream.index == 0 and tracing and \
                    now - t0 >= min(TRACE_SECONDS, seconds):
                jax.profiler.stop_trace()
                tracing = False
            if now - t0 >= seconds:
                ends[stream.index] = now
                return

    try:
        each_stream(streams, loop)
    finally:
        if tracing:
            jax.profiler.stop_trace()
    return {"started": t0, "ended": max(ends.values()),
            "seconds": max(ends.values()) - t0}


def check_answers(cell, data_dir, done) -> tuple:
    """Every answer of the window against the reference's, per query the
    worst of each number compared, printed beside its limit."""
    import pandas as pd

    import reference

    os.makedirs(os.path.join(data_dir, "answers"), exist_ok=True)
    all_ok, wrong = True, 0
    for q, spec in cell["queries"].items():
        kept = os.path.join(data_dir, "answers", q + ".parquet")
        if os.path.exists(kept):
            want = pd.read_parquet(kept)
        else:
            want = reference.query(q)(data_dir)
            want.to_parquet(kept + ".tmp")
            os.replace(kept + ".tmp", kept)
        worst = {k: 0 for k in spec["limits"]}
        answers = [frame for name, _, _, frame, _ in done
                   if name == q and frame is not None]
        for frame in answers:
            got = reference.compare(frame, want, spec["quotient_columns"])
            bad = any(got[k] > spec["limits"][k] for k in worst)
            wrong += bad
            worst = {k: max(worst[k], got[k]) for k in worst}
        ok = bool(answers) and all(
            worst[k] <= spec["limits"][k] for k in worst)
        all_ok = all_ok and ok
        say({"phase": "check", "query": q, "answers": len(answers),
             "ok": ok, **{k: {"value": worst[k], "limit": spec["limits"][k]}
                          for k in worst}})
    return all_ok, wrong


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at the configuration's "
                         "rehearse_scale; the line says it is a rehearsal")
    args = ap.parse_args(argv)

    cell = find_cell(args.workload)
    config = cell["config"]
    # deployment settings the configuration states (the program reads them
    # from the environment), in place before the program is imported
    os.environ.update(config.get("environment", {}))
    sys.path.insert(0, ROOT)
    import ballista_tpu  # noqa: F401 - places the compile cache first
    import jax

    import engine
    import traffic
    import xplane

    traffic.check(cell["traffic"])
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not args.rehearse:
        print(f"run.py: JAX found no TPU (devices: {device}); the benchmark "
              "does not fall back to the CPU", file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} chip(s), JAX "
              f"reports {len(devices)}", file=sys.stderr)
        return 1

    data_dir, tables, made, data_s = cell_data(cell, args.seed, args.rehearse)
    say({"phase": "data", "dir": os.path.relpath(data_dir, ROOT),
         "made": made, "seconds": data_s,
         "compile_cache_dir": jax.config.jax_compilation_cache_dir})

    trace_dir = (os.path.join(TRACE_ROOT, cell["name"])
                 if args.trace else None)
    readers = {name: load_reader(name) for name in
               cell["reports"]["per_layer" if args.trace else "end_to_end"]}
    after_query = {name: r.after_query for name, r in readers.items()
                   if hasattr(r, "after_query")} if args.trace else None

    def snapshots() -> dict:
        return {name: r.snapshot() for name, r in readers.items()
                if hasattr(r, "snapshot")}

    eng = engine.Engine(config, data_dir, tables)
    try:
        streams = [Stream(i, eng.context(), cell, args.seed, after_query)
                   for i in range(int(cell["traffic"]["streams"]))]
        warm = warm_up(streams, int(config["warm_rounds_max"]))
        at_window, snaps = engine.counters(), snapshots()
        window = measure(streams, args.seconds, trace_dir)
        in_window = engine.delta(at_window, engine.counters())
        snaps = {name: (snaps[name], after)
                 for name, after in snapshots().items()}
        peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                 for d in devices[:cell["chips"]]]
    finally:
        eng.close()

    done = [d for s in streams for d in s.done]
    say({"phase": "window", "seconds": window["seconds"],
         "queries": len(done),
         "compiles_in_window": in_window["backend_compiles"]})
    planes = xplane.load(trace_dir) if trace_dir else None
    traced = xplane.reduce(planes, cell["chips"]) if trace_dir else None
    answers_ok, wrong = check_answers(cell, data_dir, done)
    failed = sum(1 for d in done if d[3] is None) + wrong
    if not warm["settled"]:
        say({"phase": "warm_up", "settled": False, "rounds": warm["rounds"],
             "note": "still compiling after warm_rounds_max rounds"})

    import bytes_model

    obs = {
        "cell": cell, "device": device, "peak_bytes": max(peaks),
        "setup": {"seconds": window["started"] - T_START,
                  "counters": at_window, **warm},
        "window": {"started": window["started"], "ended": window["ended"],
                   "seconds": window["seconds"], "counters": in_window,
                   "queries": [{"query": q, "started": t, "seconds": s,
                                "record": r}
                               for q, t, s, f, r in done if f is not None]},
        # per reader with a ``snapshot``: (at the window's start, after it)
        "snapshots": snaps,
        # the traced rounds: the reduction the shipped readers share, and
        # the profiler's planes and directory for a reader of its own
        "trace": traced, "planes": planes, "trace_dir": trace_dir,
        "query_bytes": {q: bytes_model.query_bytes(spec, data_dir)
                        for q, spec in cell["queries"].items()},
        "peaks": read_json(HERE, "peaks.json"),
    }
    metrics = {}
    for name, reader in readers.items():
        value = reader.read(obs)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": value, "unit": reader.UNIT}

    device["memory_peak_bytes"] = max(peaks)
    line = {"correct": bool(answers_ok and warm["settled"] and failed == 0),
            "attempted": len(done), "failed": failed, "metrics": metrics,
            "device": device}
    if traced is not None:
        device["busy_s"], device["window_s"] = \
            traced["busy_s"], traced["window_s"]
        line["breakdown"] = {"device_ops": traced["device_ops"],
                             "idle_gaps": traced["idle_gaps"]}
    if args.rehearse:
        line["rehearsal"] = True
    # the whole run against the 360 s a run may take (a first, compiling
    # run 1200 s); interpreter start-up, about a second, is not in it
    say({"phase": "run", "wall_s": time.time() - T_START,
         "setup_s": window["started"] - T_START})
    say(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
