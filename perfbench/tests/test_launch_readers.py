"""The readers of the program's launch spans, per-site blocked reads and
hand-outs: the four over ``span_totals()`` on hand-made snapshots and on the
program itself, ``launch_gap_share`` on the trace recorded on the chip with
launch annotations added here, and that a program without the keys, or a
trace without the annotations, gives every one of them nothing to read."""

import gzip
import json
import os

import pytest

import run
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # nanoseconds
TOTALS_READERS = ("launches_per_query", "launch_host_s_per_query",
                  "task_dispatch_s_per_query", "metrics_sync_share")


def obs_of(reader, before, after, queries=4):
    return {"snapshots": {reader: (before, after)},
            "window": {"queries": [{"query": "q3"}] * queries}}


def test_launches_and_their_host_seconds_a_query():
    before = {"launch:jit_repart_take": (100, 1.0),
              "launch:jit_join_ranges": (10, 0.5)}
    after = {"launch:jit_repart_take": (356, 1.8),
             "launch:jit_join_ranges": (42, 0.7),
             "launch:jit_sort_run": (4, 0.2)}  # first seen in the window
    count = run.load_reader("launches_per_query")
    secs = run.load_reader("launch_host_s_per_query")
    assert count.UNIT == "launches" and secs.UNIT == "s"
    assert count.read(obs_of("launches_per_query", before, after)) == \
        pytest.approx((256 + 32 + 4) / 4)
    assert secs.read(obs_of("launch_host_s_per_query", before, after)) == \
        pytest.approx((0.8 + 0.2 + 0.2) / 4)


def test_hand_out_seconds_a_query():
    reader = run.load_reader("task_dispatch_s_per_query")
    assert reader.UNIT == "s"
    got = reader.read(obs_of(
        "task_dispatch_s_per_query", {"scheduler.task_dispatch": (60, 0.36)},
        {"scheduler.task_dispatch": (132, 0.84)}, queries=12))
    assert got == pytest.approx(0.04)


def test_the_instrumentations_own_share_of_the_blocked_seconds():
    reader = run.load_reader("metrics_sync_share")
    assert reader.UNIT == "%"
    before = {"device.block": (10, 1.0), "device.block:metrics.rows": (4, 0.2),
              "device.block:join.stats": (6, 0.8)}
    after = {"device.block": (30, 3.0), "device.block:metrics.rows": (8, 0.3),
             "device.block:join.stats": (22, 2.7)}
    assert reader.read(obs_of("metrics_sync_share", before, after)) == \
        pytest.approx(100.0 * 0.1 / 2.0)
    # sites, but no read of the metrics in the window: 0, not nothing
    quiet = {k: v for k, v in after.items() if "metrics" not in k}
    assert reader.read(obs_of("metrics_sync_share", {}, quiet)) == 0.0
    # no second blocked in the window: no share to give
    assert reader.read(obs_of("metrics_sync_share", after, after)) is None


@pytest.mark.parametrize("name", TOTALS_READERS)
def test_a_program_without_the_keys_gives_nothing_to_read(name, monkeypatch):
    reader = run.load_reader(name)
    # the parent: totals by name alone, no launch span, standalone
    older = {"device.block": (30, 3.0)} if name == "metrics_sync_share" \
        else {}
    assert reader.read(obs_of(name, older, older)) is None
    assert reader.read(obs_of(name, None, None)) is None
    if name != "metrics_sync_share":  # a rate needs the window's queries
        full = {"launch:jit_x": (1, 0.1), "scheduler.task_dispatch": (1, 0.1)}
        assert reader.read(obs_of(name, full, full, queries=0)) is None
    from ballista_tpu.observability import tracing

    assert isinstance(reader.snapshot(), dict)
    monkeypatch.delattr(tracing, "span_totals")
    assert reader.snapshot() is None  # a program that keeps no totals


def test_the_snapshots_read_the_programs_own_totals():
    import jax.numpy as jnp

    from ballista_tpu.compile.governor import governed
    from ballista_tpu.observability.tracing import trace_span

    fn = governed(("readertest.double", 39), lambda: lambda x: x * 2)
    fn(jnp.arange(4))
    readers = {n: run.load_reader(n) for n in TOTALS_READERS}
    before = {n: r.snapshot() for n, r in readers.items()}
    for _ in range(6):
        fn(jnp.arange(4))
    for site in ("metrics.rows", "join.stats"):
        with trace_span("device.block", site=site):
            pass
    with trace_span("scheduler.task_dispatch", task="t"):
        pass
    obs = {"snapshots": {n: (before[n], r.snapshot())
                         for n, r in readers.items()},
           "window": {"queries": [{"query": "q3"}] * 2}}
    assert readers["launches_per_query"].read(obs) == 3.0
    assert readers["launch_host_s_per_query"].read(obs) > 0
    assert readers["task_dispatch_s_per_query"].read(obs) >= 0
    assert 0 <= readers["metrics_sync_share"].read(obs) <= 100
    assert all(k.startswith("launch:") for k in before["launches_per_query"])


# -- launch_gap_share, on the trace recorded on the chip ----------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz")) as fh:
        return json.load(fh)


def idle_of(planes):
    """The first chip's idle intervals in the traced window, by the plain
    route: the window less the union of its operations."""
    notes = [ev for p in planes if not p["name"].startswith("/device:")
             for ln in p["lines"] for ev in ln["events"]
             if ev[0].startswith("collect:")]
    w0, w1 = min(s for _, s, _ in notes), max(s + d for _, s, d in notes)
    dev = next(p for p in planes if p["name"] == "/device:TPU:0")
    busy = xplane.union(
        [max(s, w0), min(s + d, w1)] for ln in dev["lines"]
        if ln["name"] in xplane.OPS_LINES for _, s, d in ln["events"]
        if min(s + d, w1) > max(s, w0))
    idle, edge = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > edge:
            idle.append((edge, s))
        edge = max(edge, e)
    return idle, busy, (w0, w1)


def with_launches(planes, events):
    """The trace with one more host line: the program's launch spans."""
    return planes + [{"name": "/host:launches", "lines": [
        {"name": "task-thread", "events": events}]}]


def read_gap_share(planes):
    return run.load_reader("launch_gap_share").read(
        {"planes": planes, "cell": {"chips": 1}})


def test_launch_gap_share_on_the_recorded_trace(recorded):
    assert run.load_reader("launch_gap_share").UNIT == "%"
    idle, busy, (w0, w1) = idle_of(recorded)
    assert len(idle) > 10
    # the parent's trace: no launch annotation, nothing to read
    assert read_gap_share(recorded) is None
    # a launch over the first half of every idle gap, named as the program's
    # spans are; a device.block over the second half does not count
    halves = [["launch:jit_agg_grouped_don", s, (e - s) // 2]
              for s, e in idle]
    blocks = [["device.block:batch.to_pydict", s + (e - s) // 2, (e - s) // 2]
              for s, e in idle]
    want = 100.0 * sum(d for _, _, d in halves) / sum(e - s for s, e in idle)
    assert 49.0 < want <= 50.0
    assert read_gap_share(with_launches(recorded, halves + blocks)) == \
        pytest.approx(want, rel=1e-9)
    # the OUTERMOST span names the gap: runtime events inside it change nothing
    inner = [["DeferredTpuAllocator::Allocate", s + 1, max(d - 2, 0)]
             for _, s, d in halves]
    assert read_gap_share(with_launches(recorded, halves + inner)) == \
        pytest.approx(want, rel=1e-9)
    # one launch over the whole window, and two that overlap: all of it, once
    assert read_gap_share(with_launches(
        recorded, [["launch:jit_a", w0 - MS, w1 - w0 + 2 * MS],
                   ["launch:jit_b", w0, (w1 - w0) // 2]])) == \
        pytest.approx(100.0)
    # launches only while the device is busy: 0, not nothing
    assert read_gap_share(with_launches(
        recorded, [["launch:jit_a", s, e - s] for s, e in busy])) == 0.0
    # a cold launch is the compile's, not the window's dispatch
    assert read_gap_share(with_launches(
        recorded, [["launch.cold:jit_a", w0, w1 - w0]])) is None


def test_launch_gap_share_needs_a_device_plane_and_a_traced_query(recorded):
    launches = [["launch:jit_a", 0, 10 * MS]]
    host_only = [p for p in recorded if not p["name"].startswith("/device:")]
    assert read_gap_share(with_launches(host_only, launches)) is None
    assert run.load_reader("launch_gap_share").read(
        {"planes": None, "cell": {"chips": 1}}) is None  # --trace 0
    no_query = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [ev for ev in ln["events"]
                                        if not ev[0].startswith("collect:")]}
        for ln in p["lines"]]} for p in recorded]
    assert read_gap_share(with_launches(no_query, launches)) is None
