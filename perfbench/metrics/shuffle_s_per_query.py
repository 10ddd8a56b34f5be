"""Task thread-seconds a query spent writing and fetching shuffle
partitions (ledger ``shuffle_write`` + ``shuffle_fetch``): a SUM over tasks
that run side by side, not wall time."""

from _common import phase_mean

UNIT = "s"


def read(obs):
    return phase_mean(obs, "shuffle_write", "shuffle_fetch")
