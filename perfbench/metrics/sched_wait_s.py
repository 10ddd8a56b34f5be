"""Seconds a query waited in the scheduler's admission gate and queue, from
the program's latency ledger. Blind to the executors' and the client's polls
(a span for those is an open question in PERF.md)."""

from _common import phase_mean

UNIT = "s"


def read(obs):
    return phase_mean(obs, "admission_wait", "queue_wait")
