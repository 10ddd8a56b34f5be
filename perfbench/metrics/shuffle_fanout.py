"""Destinations a shuffling task of the window wrote to: the mean
``fan_out`` of the program's ``shuffle.write`` events, over every task that
wrote shuffled output in the window's recorded queries. It is the
``join.partitions`` the planner chose, which the control plane's cost
feedback moves after the first run of a query (8 by default). A program
without the event gives nothing to read."""

import _shuffle_write

UNIT = "partitions"


def read(obs):
    per_query = _shuffle_write.per_query(obs)
    if per_query is None:
        return None
    return (sum(q["fan_out"] for q in per_query)
            / sum(q["tasks"] for q in per_query))
