"""Rows the window's scans emitted, as a share of the rows of the tables
its queries read: the ``rows`` of the program's ``scan.serve`` events over,
for every query of the window, the rows in the files of each table its
``reads`` names (the files' metadata, as ``bytes_model.py`` counts them).
100% is every query scanning all of its tables once; a reading far below
that is NOT pruning here: a cached plan keeps a join's build side and a
repartition's sorted sources between executions, so a warm query does not
scan the tables under them again (PERF.md section 6, PR 41). A program
without the event gives nothing to read."""

import os

import pyarrow.parquet as pq

import _plan_events

UNIT = "%"

after_query = _plan_events.after_query


def table_rows(data_dir: str, table: str) -> int:
    base = os.path.join(data_dir, table)
    return sum(pq.ParquetFile(os.path.join(base, f)).metadata.num_rows
               for f in sorted(os.listdir(base)) if f.endswith(".parquet"))


def read(obs):
    import mesh_bytes

    per_query = _plan_events.per_query(obs, "scan.serve")
    if per_query is None:
        return None
    data_dir = mesh_bytes.data_dir_of(obs)
    of_query = {q: sum(table_rows(data_dir, t) for t in spec["reads"])
                for q, spec in obs["cell"]["queries"].items()}
    scanned = sum(int(e["rows"] or 0) for _, events in per_query
                  for e in events)
    return 100.0 * scanned / sum(of_query[q] for q, _ in per_query)
