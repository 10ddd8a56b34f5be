"""Seconds a query's task threads spent reading shuffled input back: the
seconds of the program's ``shuffle.read`` spans (one a reader's partition
loaded: its producers' files decoded, from this host's disk where they are
here, and the upload enqueued; not the upload's completion), the window's
delta over its queries. A sum over the executors' task threads. A program
without the span gives nothing to read."""

import _totals

UNIT = "s"
SPAN = "shuffle.read"


def snapshot():
    return _totals.snapshot(SPAN)


def read(obs):
    return _totals.a_query(obs, "shuffle_read_s_per_query", SPAN, 1)
