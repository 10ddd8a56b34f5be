"""The reduction from a profiler trace to busy time, costliest programs and
named idle gaps: on a hand-made trace whose answer is plain to see, and on a
small trace recorded on the chip (``recorded_trace.json.gz``: the first
round, 82 ms, of a ``standalone-scanagg`` run on a TPU v5 lite, PR 24)."""

import gzip
import json
import os

import pytest

import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000


def _planes(ops, modules, host, async_ops=()):
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
            {"name": "Async XLA Ops", "events": list(async_ops)}]},
        {"name": "/host:CPU", "lines": host},
        {"name": "/host:metadata", "lines": []}]


def test_hand_made_trace():
    # two queries, 0-100 ms and 100-200 ms; the device works 10-30 ms,
    # 20-40 ms (overlapping: union 10-40) and 150-160 ms
    planes = _planes(
        ops=[["fusion.1", 10 * MS, 20 * MS], ["fusion.2", 20 * MS, 20 * MS],
             ["fusion.3", 150 * MS, 10 * MS]],
        modules=[["jit_scan(123)", 10 * MS, 30 * MS],
                 ["jit_agg(77)", 150 * MS, 10 * MS]],
        host=[{"name": "main", "events": [
                  ["collect:q1", 0, 100 * MS], ["collect:q6", 100 * MS, 100 * MS],
                  ["Dispatch", 40 * MS, 30 * MS], ["Inner", 50 * MS, 10 * MS]]},
              {"name": "worker", "events": [["Fetch", 160 * MS, 20 * MS]]}])
    got = xplane.reduce(planes)
    assert got["busy_s"] == pytest.approx(0.040)
    assert got["window_s"] == pytest.approx(0.200)
    assert got["queries"] == 2
    assert got["device_ops"] == [["jit_scan", pytest.approx(0.030)],
                                 ["jit_agg", pytest.approx(0.010)]]
    idle = dict(got["idle_gaps"])
    # gaps: 0-10, 40-150 (cut by the annotation at its midpoint: q1), 160-200
    assert idle["collect:q1_Dispatch"] == pytest.approx(0.020)  # 40-50, 60-70
    assert idle["collect:q1_Inner"] == pytest.approx(0.010)     # innermost
    assert idle["collect:q1_no_traced_host_event"] == pytest.approx(0.090)
    assert idle["collect:q6_Fetch"] == pytest.approx(0.020)
    assert idle["collect:q6_no_traced_host_event"] == pytest.approx(0.020)
    assert sum(idle.values()) == pytest.approx(0.200 - 0.040)


def test_async_copies_count_as_busy_and_cpu_traces_reduce_to_none():
    planes = _planes(ops=[["f", 0, 10 * MS]], modules=[["jit_f(1)", 0, 10 * MS]],
                     host=[{"name": "main", "events": [["collect:q1", 0, 40 * MS]]}],
                     async_ops=[["copy-start", 5 * MS, 15 * MS]])
    assert xplane.reduce(planes)["busy_s"] == pytest.approx(0.020)
    assert xplane.reduce(planes[1:]) is None
    assert xplane.union([[5, 9], [1, 3], [2, 6], [20, 20]]) == [[1, 9]]


def test_recorded_chip_trace():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz")) as fh:
        planes = json.load(fh)
    got = xplane.reduce(planes)
    with open(os.path.join(HERE, "recorded_trace.expected.json")) as fh:
        want = json.load(fh)
    assert got["queries"] == want["queries"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
    # busy, by a second route: paint every operation onto a 1 us grid
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    anns = [ev for p in planes if p["name"] == "/host:CPU"
            for ln in p["lines"] for ev in ln["events"]
            if ev[0].startswith("collect:")]
    w0 = min(a[1] for a in anns)
    w1 = max(a[1] + a[2] for a in anns)
    grid = bytearray((w1 - w0) // 1000 + 2)
    for ln in dev["lines"]:
        if ln["name"] in xplane.OPS_LINES:
            for _, s, d in ln["events"]:
                lo, hi = max(s, w0), min(s + d, w1)
                if hi > lo:
                    a, b = (lo - w0) // 1000, -(-(hi - w0) // 1000)
                    grid[a:b] = b"\x01" * (b - a)
    painted = sum(grid) * 1e-6
    assert got["busy_s"] <= painted <= got["busy_s"] * 1.25 + 1e-4
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9
    assert 0 < got["busy_s"] < got["window_s"]
