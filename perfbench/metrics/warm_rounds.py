"""Warm-up rounds run until two in a row compiled nothing."""

UNIT = "rounds"


def read(obs):
    return float(obs["setup"]["rounds"])
