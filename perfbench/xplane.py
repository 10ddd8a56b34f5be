"""From a profiler trace to device busy time, the device's costliest
programs, and the idle gaps named by what the host was doing.

``load`` turns the profiler's ``.xplane.pb`` into plain data (planes, lines,
events in nanoseconds); ``reduce`` works on that plain data alone, so the
same reduction is checked against the small recorded trace kept in
``tests/``. A trace with no device plane (a CPU rehearsal) reduces to
``None``: there is then no device number to print.
"""

from __future__ import annotations

import glob
import heapq
import os
import re
from bisect import bisect_right

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# device lines: one event per operation run on the core, per asynchronous
# copy, and per program (jit_<name>(<id>)); the first two make "busy"
OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
ANNOTATION = "collect:"       # run.py wraps every query in one of these
MIN_GAP_NS = 20_000           # shorter gaps are summed as "short_gaps"


def profiler_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # per-call Python events swamp the trace
    opts.host_tracer_level = 2
    return opts


def load(log_dir: str) -> list:
    """The newest ``.xplane.pb`` under ``log_dir`` as
    ``[{"name", "lines": [{"name", "events": [[name, start_ns, dur_ns]]}]}]``."""
    from jax.profiler import ProfileData

    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(found[-1]).planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return out


def _innermost(events) -> list:
    """Flatten overlapping host events (any thread) into disjoint
    ``(start, end, name)`` segments, each named by the event that started
    last among those running there."""
    events = sorted(events, key=lambda e: e[1])
    bounds = sorted({e[1] for e in events} | {e[1] + e[2] for e in events})
    out, active, i = [], [], 0  # active: heap of (-start, end, name)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        while i < len(events) and events[i][1] <= lo:
            name, s, d = events[i]
            heapq.heappush(active, (-s, s + d, name))
            i += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        if active:
            out.append((lo, hi, active[0][2]))
    return out


def _overlap_by_name(segments, ends, gs: int, ge: int) -> dict:
    """Nanoseconds of the gap [gs, ge) covered by each segment name;
    ``segments`` are disjoint and sorted, ``ends`` their end times."""
    by_name = {}
    k = bisect_right(ends, gs)
    while k < len(segments) and segments[k][0] < ge:
        s, e, name = segments[k]
        cover = min(e, ge) - max(s, gs)
        if cover > 0:
            by_name[name] = by_name.get(name, 0) + cover
        k += 1
    return by_name


def reduce(planes: list, chips: int = 1) -> dict | None:
    """``busy_s`` (union of device operation intervals, averaged over the
    chips used), ``window_s`` (first annotation's start to the last one's
    end; the device events' own span where there is no annotation),
    ``queries`` (annotations seen), ``device_ops`` and ``idle_gaps`` (each
    ``[[name, seconds], ...]``, longest first, at most 10)."""
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        return None
    host_events, annotations = [], []
    for p in planes:
        if p["name"].startswith("/device:"):
            continue
        for line in p["lines"]:
            for ev in line["events"]:
                (annotations if ev[0].startswith(ANNOTATION)
                 else host_events).append(ev)
    annotations.sort(key=lambda e: e[1])

    busy_by_chip, ops_time = [], {}
    for p in devices[:chips]:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = [ev for name in OPS_LINES for ev in lines.get(name) or []] \
            or lines.get(MODULES_LINE) or []
        busy_by_chip.append(union([s, s + d] for _, s, d in ops))
        for name, _, d in lines.get(MODULES_LINE) or ops:
            name = re.sub(r"\(\d+\)$", "", name)
            ops_time[name] = ops_time.get(name, 0) + d
    if not any(busy_by_chip):
        return None
    if annotations:
        w0 = annotations[0][1]
        w1 = max(s + d for _, s, d in annotations)
    else:
        w0 = min(iv[0][0] for iv in busy_by_chip if iv)
        w1 = max(iv[-1][1] for iv in busy_by_chip if iv)

    def clipped(iv):
        return [[max(s, w0), min(e, w1)] for s, e in iv
                if min(e, w1) > max(s, w0)]

    busy_by_chip = [clipped(iv) for iv in busy_by_chip]
    busy_ns = sum(sum(e - s for s, e in iv) for iv in busy_by_chip)
    busy_ns /= max(len(busy_by_chip), 1)

    # idle gaps of the first chip, cut by query annotation, then named by
    # the innermost host event running in them
    gaps, edge = [], w0
    for s, e in busy_by_chip[0] + [[w1, w1]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    starts = [a[1] for a in annotations]
    segments = _innermost(host_events)
    ends = [seg[1] for seg in segments]
    idle = {}
    short = 0
    for gs, ge in gaps:
        if ge - gs < MIN_GAP_NS:
            short += ge - gs
            continue
        k = bisect_right(starts, (gs + ge) // 2) - 1
        inside = k >= 0 and (gs + ge) // 2 < starts[k] + annotations[k][2]
        label = annotations[k][0] if inside else "between_queries"
        named = _overlap_by_name(segments, ends, gs, ge)
        rest = (ge - gs) - sum(named.values())
        if rest > 0:
            named["no_traced_host_event"] = rest
        for name, ns in named.items():
            key = re.sub(r"\s+", "_", f"{label}_{name}")[:64]
            idle[key] = idle.get(key, 0) + ns
    if short:
        idle["short_gaps"] = short

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"busy_s": busy_ns / 1e9, "window_s": (w1 - w0) / 1e9,
            "queries": len(annotations), "device_ops": top(ops_time),
            "idle_gaps": top(idle)}


def trimmed(planes: list, span_ns: int) -> list:
    """A small trace for ``tests/``: the events that start within
    ``span_ns`` of the first query annotation, names cut to 80 characters."""
    t0 = min(ev[1] for p in planes for ln in p["lines"]
             for ev in ln["events"] if ev[0].startswith(ANNOTATION))
    out = []
    for p in planes:
        lines = [{"name": ln["name"],
                  "events": [[ev[0][:80], ev[1], ev[2]] for ev in ln["events"]
                             if t0 <= ev[1] < t0 + span_ns]}
                 for ln in p["lines"]]
        out.append({"name": p["name"],
                    "lines": [ln for ln in lines if ln["events"]]})
    return out


if __name__ == "__main__":
    # look at one trace by hand: python perfbench/xplane.py <trace_dir>
    # [<milliseconds> <out.json>] prints the lines found and the reduction,
    # and can keep the trace's first milliseconds as plain JSON
    import json
    import sys

    loaded = load(sys.argv[1])
    for p in loaded:
        print(p["name"], [(ln["name"], len(ln["events"]))
                          for ln in p["lines"]][:12])
    print(json.dumps(reduce(loaded)))
    if len(sys.argv) > 3:
        with open(sys.argv[3], "w") as fh:
            json.dump(trimmed(loaded, int(float(sys.argv[2]) * 1e6)), fh)
