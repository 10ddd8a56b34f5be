"""Tasks the scheduler completed for a query (all stages)."""

from _common import mean, records

UNIT = "tasks"


def read(obs):
    return mean(r["tasks"] for r in records(obs))
