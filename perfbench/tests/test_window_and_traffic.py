"""The window runs whole rounds and ends on the first round boundary at or
after --seconds; rates divide by the time to that boundary; every seed gives
every round the same queries in another order."""

import itertools

import pytest

import run
import traffic

MIX = {"arrival": "closed", "streams": 1, "round": ["q1", "q6", "q3"],
       "parameters": "fixed"}


class FakeStream:
    """Rounds of two 'queries' that each take ``step`` seconds of a clock the
    test owns."""

    def __init__(self, clock, step, index=0):
        self.clock, self.step, self.index = clock, step, index
        self.done = []

    def run_round(self):
        for q in ("a", "b"):
            started = self.clock[0]
            self.clock[0] += self.step
            self.done.append((q, started, self.step, object(), None))


@pytest.mark.parametrize("step,seconds,rounds", [
    (1.0, 5.0, 3),    # 2 s a round: boundaries at 2, 4, 6 -> ends at 6
    (1.0, 6.0, 3),    # a boundary exactly at --seconds ends the window
    (4.5, 51.0, 6),   # the served join cell: 9 s a round -> 54 s
    (0.0625, 1.0, 8),
])
def test_window_ends_on_the_first_round_boundary(monkeypatch, step, seconds,
                                                 rounds):
    clock = [1000.0]
    monkeypatch.setattr(run.time, "time", lambda: clock[0])
    stream = FakeStream(clock, step)
    window = run.measure([stream], seconds, None)
    assert len(stream.done) == 2 * rounds
    assert window["seconds"] == pytest.approx(2 * rounds * step)
    assert window["seconds"] >= seconds
    assert window["seconds"] - 2 * step < seconds  # no round too many
    obs = {"window": {"seconds": window["seconds"], "queries": [
        {"query": q, "seconds": s} for q, _, s, _, _ in stream.done]}}
    per_hour = run.load_reader("queries_per_hour").read(obs)
    assert per_hour == pytest.approx(3600.0 / step)
    assert run.load_reader("query_s_geomean").read(obs) == pytest.approx(step)


def test_p95_needs_two_hundred_queries():
    reader = run.load_reader("query_s_p95")
    few = {"window": {"queries": [{"seconds": 1.0}] * 199}}
    many = {"window": {"queries": [{"seconds": float(i)}
                                   for i in range(1, 401)]}}
    assert reader.read(few) is None
    assert reader.read(many) == 380.0  # ten percent of 400 lie above 360..


def test_every_seed_gives_the_same_rounds_in_another_order():
    traffic.check(MIX)
    orders = set()
    for seed in (1, 7, 2**31 + 12345, 3_000_000_011):
        got = list(itertools.islice(traffic.rounds(MIX, seed, 0), 50))
        assert all(sorted(r) == sorted(MIX["round"]) for r in got)
        again = list(itertools.islice(traffic.rounds(MIX, seed, 0), 50))
        assert got == again
        orders.add(tuple(map(tuple, got)))
    assert len(orders) == 4


@pytest.mark.parametrize("key,value", [("arrival", "open"),
                                       ("parameters", "drawn")])
def test_unknown_traffic_values_are_errors(key, value):
    with pytest.raises(ValueError):
        traffic.check(dict(MIX, **{key: value}))
