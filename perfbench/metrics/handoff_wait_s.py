"""Seconds of a served query's wall time spent in the hand-off between
scheduler, executors and client, from the program's latency ledger: ready
tasks waiting for an executor's poll (``dispatch_wait``, scheduler clock), the
report that completed a stage waiting for its executor's next poll
(``report_wait``, executor clock), and the finished job waiting for the
client's next status poll (``client_poll_wait``, scheduler clock). The program
keeps the three disjoint and each as wall time. A program whose ledger has no
such phases gives nothing to read."""

from _common import phase_mean, records

UNIT = "s"
PHASES = ("dispatch_wait", "report_wait", "client_poll_wait")


def read(obs):
    if not any(p in r["phases"] for r in records(obs) for p in PHASES):
        return None
    return phase_mean(obs, *PHASES)
