"""Which programs a device ran, by name, from a profiler trace: the eager
jax operations (``jit_concatenate``, ``jit_add``, ...) beside the governed
ones (``jit_agg_grouped``, ...), which no span of the program counts.

    python dev/trace_programs.py perfbench_trace/<cell>

Reads the newest ``.xplane.pb`` under the directory (a ``--trace 1`` run of
``perfbench/run.py`` leaves one there) through ``perfbench/xplane.py`` and
prints one JSON line: the queries traced (``collect:`` annotations), and per
program on the first device plane's ``XLA Modules`` line its calls, calls a
query, seconds, and ms a call. A program is governed where the host lines
hold a ``launch:<program>`` annotation (``compile/governor.py`` opens one
around every governed call), else eager; launches a query summed by kind.
"""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "perfbench")]

def programs(planes: list):
    """{program: [calls, seconds]} from the first device plane's
    ``XLA Modules`` line, or None where the trace has no device plane."""
    import xplane

    for p in planes:
        if not xplane.DEVICE_PLANE.match(p["name"]):
            continue
        by_name = {}
        for ln in p["lines"]:
            if ln["name"] != xplane.MODULES_LINE:
                continue
            for name, _, dur in ln["events"]:
                acc = by_name.setdefault(re.sub(r"\(\d+\)$", "", name), [0, 0])
                acc[0] += 1
                acc[1] += dur / 1e9
        return by_name
    return None


def main() -> int:
    import xplane

    planes = xplane.load(sys.argv[1])
    host = [ev[0] for p in planes if not p["name"].startswith("/device:")
            for ln in p["lines"] for ev in ln["events"]]
    queries = sum(name.startswith(xplane.ANNOTATION) for name in host)
    governed = {name[len("launch:"):] for name in host
                if name.startswith("launch:")}
    found = programs(planes)
    if found is None:
        print(json.dumps({"queries": queries, "programs": "not measured"}))
        return 1
    per = max(queries, 1)
    rows = {n: [c, round(c / per, 2), round(s, 6), round(s / c * 1e3, 4)]
            for n, (c, s) in sorted(found.items(), key=lambda kv: -kv[1][1])}
    split = {"governed": 0.0, "eager": 0.0}
    for n, (c, _) in found.items():
        split["governed" if n in governed else "eager"] += c / per
    print(json.dumps({"queries": queries,
                      "launches_a_query": {k: round(v, 2)
                                           for k, v in split.items()},
                      "programs": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
