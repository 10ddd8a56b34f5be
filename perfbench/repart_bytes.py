"""The bytes a repartition's takes have to move: for every destination
partition gathered (one ``repart.take`` event of the program, with the
``side`` it gathers from and the live ``rows`` it moves), the rows times
the width of a row of that side on the device, read once and written once.
A side is named by the tables under it (``lineitem``, ``customer+orders``
for the output of their join), and its row is every column the query reads
of those tables (``queries/<q>.json`` ``reads``, as ``bytes_model.py``
counts them, at ``bytes_model.width``): what the side carries through the
repartition, whatever the program lays beside it (selection masks,
validity, padding up to a capacity). Reckoned from the query's file and
the data's schema, so that a share of the memory's peak reads the same
work whatever implements the take; kept with the benchmark so that no
later PR can change what the share is a share of.
"""

from __future__ import annotations

import os

import pyarrow.parquet as pq

import bytes_model

READ_AND_WRITE = 2


def row_bytes(spec: dict, data_dir: str, side: str) -> int:
    """Width on the device of one row of ``side`` (table names joined by
    ``+``): the columns ``spec`` reads of each of its tables. A side that
    names a table the query does not read is an error, not a default."""
    total = 0
    for table in side.split("+"):
        if table not in spec["reads"]:
            raise KeyError(f"side {side!r}: the query reads no table "
                           f"{table!r} (it reads {sorted(spec['reads'])})")
        base = os.path.join(data_dir, table)
        first = sorted(f for f in os.listdir(base) if f.endswith(".parquet"))[0]
        schema = pq.ParquetFile(os.path.join(base, first)).schema_arrow
        total += sum(bytes_model.width(schema.field(c).type)
                     for c in spec["reads"][table])
    return total


def take_bytes(spec: dict, data_dir: str, takes) -> int:
    """Bytes the ``takes`` of one execution (``[{"side", "rows"}]``) have
    to move: each row read from its source and written to its partition."""
    widths = {}
    total = 0
    for t in takes:
        side = t["side"]
        if side not in widths:
            widths[side] = row_bytes(spec, data_dir, side)
        total += int(t["rows"] or 0) * widths[side] * READ_AND_WRITE
    return total
