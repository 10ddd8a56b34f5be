"""Seconds a query keeps the device busy: the union of the device's
operation intervals in the traced rounds, over the queries traced."""

UNIT = "s"


def read(obs):
    t = obs["trace"]
    return t["busy_s"] / t["queries"] if t and t["queries"] else None
