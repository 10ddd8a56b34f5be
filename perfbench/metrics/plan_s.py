"""Planning seconds a query, from the program's latency ledger."""

from _common import phase_mean

UNIT = "s"


def read(obs):
    return phase_mean(obs, "planning")
