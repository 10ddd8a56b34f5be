"""The readers of the program's hand-off phases, span totals and named idle
gaps, each on a hand-made ``obs``: what they compute, and that a program
without the phase, the totals or a device trace gives them nothing to read."""

import pytest

import run


def query(phases):
    return {"query": "q1", "started": 0.0, "seconds": 1.0,
            "record": {"phases": phases, "readers": {}}}


def test_handoff_wait_sums_the_three_phases_a_query():
    reader = run.load_reader("handoff_wait_s")
    obs = {"window": {"queries": [
        query({"dispatch_wait": 0.25, "report_wait": 0.5,
               "client_poll_wait": 0.05, "planning": 9.0}),
        query({"dispatch_wait": 0.15, "report_wait": 0.25,
               "client_poll_wait": 0.0})]}}
    assert reader.read(obs) == pytest.approx((0.8 + 0.4) / 2)
    # the parent's ledger has no such phase: nothing to read, not 0
    older = {"window": {"queries": [query({"planning": 0.1,
                                           "queue_wait": 0.2})]}}
    assert reader.read(older) is None
    assert reader.read({"window": {"queries": []}}) is None


def test_result_fetch_is_transfer_plus_decode():
    reader = run.load_reader("result_fetch_s")
    obs = {"window": {"queries": [
        query({"result_transfer": 0.002, "host_decode": 0.004}),
        query({"result_transfer": 0.004, "host_decode": 0.006,
               "device_execute": 5.0})]}}
    assert reader.read(obs) == pytest.approx(0.008)


def test_sync_wait_is_the_windows_delta_over_its_queries():
    reader = run.load_reader("sync_wait_s_per_query")
    obs = {"snapshots": {"sync_wait_s_per_query": (10.0, 16.0)},
           "window": {"queries": [query({})] * 4}}
    assert reader.read(obs) == pytest.approx(1.5)
    obs["snapshots"]["sync_wait_s_per_query"] = (None, None)
    assert reader.read(obs) is None  # a program without span totals
    assert isinstance(reader.snapshot(), float)


def test_tasks_speculated_counts_the_windows_events():
    reader = run.load_reader("tasks_speculated")
    assert reader.read({"snapshots": {"tasks_speculated": (3, 4)}}) == 1.0
    assert reader.read({"snapshots": {"tasks_speculated": (3, 3)}}) == 0.0
    assert reader.read({"snapshots": {"tasks_speculated": (None, None)}}) \
        is None
    assert isinstance(reader.snapshot(), int)


def test_snapshots_find_nothing_in_a_program_without_span_totals(monkeypatch):
    from ballista_tpu.observability import tracing

    monkeypatch.delattr(tracing, "span_totals")
    assert run.load_reader("sync_wait_s_per_query").snapshot() is None
    assert run.load_reader("tasks_speculated").snapshot() is None


def test_unnamed_idle_share_is_the_unnamed_part_of_the_idle_time():
    reader = run.load_reader("unnamed_idle_share")
    trace = {"busy_s": 0.03, "window_s": 5.06, "queries": 4, "device_ops": [],
             "idle_gaps": [["collect:q1_no_traced_host_event", 2.399],
                           ["collect:q6_no_traced_host_event", 2.249],
                           ["collect:q1_np.asarray(jax.Array)", 0.12],
                           ["short_gaps", 0.001]]}
    assert reader.read({"trace": trace}) == pytest.approx(
        100.0 * (2.399 + 2.249) / 5.03)
    named = dict(trace, idle_gaps=[["collect:q1_executor.poll_wait", 2.5],
                                   ["collect:q6_client.poll_wait", 2.4]])
    assert reader.read({"trace": named}) == 0.0
    assert reader.read({"trace": None}) is None  # a rehearsal: no device
    assert reader.read({"trace": dict(trace, busy_s=5.06)}) is None
