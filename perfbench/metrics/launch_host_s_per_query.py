"""Seconds a query's host threads spent launching programs: the seconds of
the program's ``launch:<program>`` spans, each from the call of a jitted
function to its return (jax's dispatch, output allocation, the enqueue; not
the program's run on the device, which is asynchronous), the window's delta
over its queries. A sum over the executors' task threads in a served cell,
so it can pass the query's wall time there. A program without launch spans
gives nothing to read."""

import _totals

UNIT = "s"
PREFIX = "launch:"


def snapshot():
    return _totals.snapshot(PREFIX)


def read(obs):
    return _totals.a_query(obs, "launch_host_s_per_query", PREFIX, 1)
