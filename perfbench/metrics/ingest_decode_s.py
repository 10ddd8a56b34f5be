"""Thread-seconds the program spent parsing Parquet into columns during
set-up (``ingest.phase_totals()["parse"]``): a sum over threads, so it can
exceed the wall time."""

UNIT = "s"


def read(obs):
    return obs["setup"]["counters"]["parse_seconds"]
