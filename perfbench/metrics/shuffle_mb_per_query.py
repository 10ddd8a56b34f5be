"""Megabytes a query's non-final stages wrote into the shuffle data plane."""

from _common import mean, records

UNIT = "MB"


def read(obs):
    got = mean(r["shuffle_bytes"] for r in records(obs))
    return None if got is None else got / 1e6
