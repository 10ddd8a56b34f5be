"""Slices a query's shuffling tasks handed to the Arrow encoder: every batch
a task produces goes to ``ipc.batch_to_arrow`` once a destination, so a
task's slices are its batches x its fan-out (the ``slices`` of the program's
``shuffle.write`` event), and each costs a blocking read of the mask and one
of every column. A mean over the window's recorded queries, of the sums over
their shuffling tasks. A program without the event gives nothing to read."""

import _shuffle_write
from _common import mean

UNIT = "slices"


def read(obs):
    per_query = _shuffle_write.per_query(obs)
    if per_query is None:
        return None
    return mean(q["slices"] for q in per_query)
