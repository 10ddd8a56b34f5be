"""Each query's first execution in the process (ingest, compile or cache
load, upload), summed over the cell's queries. Part of set-up."""

UNIT = "s"


def read(obs):
    cold = obs["setup"]["cold_query_s"]
    return sum(cold.values()) if cold else None
