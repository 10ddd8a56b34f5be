"""Seconds a query's task threads spent inside the blocking reads of its
shuffle writes: the seconds of the program's ``device.block`` spans at the
site ``ipc.batch_to_arrow``, the window's delta over its queries. A sum over
the executors' task threads, so it can pass the query's wall time. A program
whose totals have no such key gives nothing to read."""

import _totals

UNIT = "s"
SPAN = "device.block:ipc.batch_to_arrow"


def snapshot():
    return _totals.snapshot(SPAN)


def read(obs):
    return _totals.a_query(obs, "shuffle_d2h_wait_s_per_query", SPAN, 1)
