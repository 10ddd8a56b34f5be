"""Seconds a query's task threads spent making join build sides: the seconds
of the program's ``join.build`` spans (a build side's subtree run, its pieces
concatenated and sorted or tabled), the window's delta over its queries. A
sum over the executors' task threads. A served task deserialises its own
plan, so every build of every query is made and none reused; a standalone
context that keeps its plans reads 0 here. A program without the span gives
nothing to read."""

import _totals

UNIT = "s"
SPAN = "join.build"


def snapshot():
    got = _totals.snapshot(SPAN)
    # the name itself: ``join.build_reused`` starts with it too
    return None if got is None else {k: v for k, v in got.items()
                                     if k == SPAN}


def read(obs):
    return _totals.a_query(obs, "join_build_s_per_query", SPAN, 1)
