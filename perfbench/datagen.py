"""TPC-H-like data straight to Parquet, made from a seed.

The distributions, key layout and correlations are those of the repo's
``benchmarks/tpch/datagen.py`` (dbgen-like, not dbgen: date-correlated
returnflag/linestatus, price = f(partkey), sparse order keys, no orders for
custkey % 3 == 0). What differs is the route: that generator formats ``.tbl``
text through ``np.char`` (219 s at SF1 on the chip host); this one draws
integer codes, turns them into Arrow string columns through small
dictionaries, and writes Parquet a chunk at a time, one thread a file.

A table is a file of its own, ``tables/<table>.py``, found by name
(``table``). It holds the table's ``ARROW_SCHEMA`` (the Parquet files),
``program_schema()`` (the same columns in the program's own types) and
``PRIMARY_KEY`` (or None), and either makes its own rows (``SEED_ID``, a
number no other table has; ``rows(scale)``; ``chunk(rng, lo, hi, scale)``,
which returns ``{table: [Arrow column, ...]}`` for rows [lo, hi)) or names
in ``MADE_BY`` the table whose ``chunk`` makes it alongside (lineitem comes
out of orders' chunks). This file is what they share: the word lists, the
column helpers and the one ``generate``.

Every chunk has a generator of its own, seeded from (seed, table, chunk), so
the files do not depend on thread timing: the same seed gives the same bytes.
Money columns are float64 in the files, as upstream's own converted Parquet
has them; the program's schemas read them as DECIMAL(2).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

import byname

# Bump when the generated DISTRIBUTION or file layout changes: the data
# directory's name carries it, so older data is never reused.
DATAGEN_VERSION = 1

NOUNS = ["packages", "requests", "accounts", "deposits", "foxes", "ideas",
         "theodolites", "pinto beans", "instructions", "dependencies"]
VERBS = ["sleep", "wake", "haggle", "nag", "cajole", "detect", "integrate",
         "boost", "doze", "wake blithely"]
COMMENTS = [f"{n} {v} #{k}" for n in NOUNS for v in VERBS
            for k in range(1000)]


def table(name: str):
    """``tables/<name>.py``."""
    return byname.load("tables", name)


def strings(codes, values) -> pa.Array:
    """Integer codes into ``values`` -> an Arrow string column."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(values, type=pa.string())).cast(pa.string())


def tagged(prefix: str, numbers) -> pa.Array:
    return pc.binary_join_element_wise(
        pa.scalar(prefix), pc.cast(pa.array(numbers), pa.string()), "")


def money(rng, n, lo, hi):
    return rng.integers(int(lo * 100), int(hi * 100), n) / 100.0


def comments(rng, n) -> pa.Array:
    return strings(rng.integers(0, len(COMMENTS), n), COMMENTS)


def dates(days) -> pa.Array:
    return pa.array(days.astype("datetime64[D]").astype(np.int32),
                    type=pa.int32()).cast(pa.date32())


def _chunks(n_rows: int, files: int, chunk_rows: int):
    """Row ranges: a multiple of ``files`` chunks, so every file gets the
    same number of them (fewer only when there are fewer rows than files)."""
    per_file = max(1, -(-n_rows // (files * chunk_rows)))
    count = min(files * per_file, max(n_rows, 1))
    edges = np.linspace(0, n_rows, count + 1).astype(np.int64)
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])]


def generate(data_dir: str, scale: float, tables, files: int, seed: int,
             chunk_rows: int = 250_000) -> None:
    """Write ``tables`` under ``data_dir`` as ``<table>/part-<k>.parquet``,
    ``files`` files a table.

    One thread a file: it makes and writes that file's chunks in order
    (chunk i of a table belongs to file i % files), so memory stays at a
    chunk a thread and the bytes do not depend on timing."""
    kept = {}  # the table whose chunks are drawn -> the tables kept of them
    for t in tables:
        kept.setdefault(getattr(table(t), "MADE_BY", t), []).append(t)

    def write_file(maker, keep, k):
        writers = {}
        try:
            for t in keep:
                os.makedirs(os.path.join(data_dir, t), exist_ok=True)
                writers[t] = pq.ParquetWriter(
                    os.path.join(data_dir, t, f"part-{k}.parquet"),
                    table(t).ARROW_SCHEMA, compression="snappy")
            chunks = _chunks(maker.rows(scale), files, chunk_rows)
            for i in range(k, len(chunks), files):
                rng = np.random.default_rng([seed, maker.SEED_ID, i])
                made = maker.chunk(rng, *chunks[i], scale)
                for t in keep:
                    writers[t].write_table(pa.Table.from_arrays(
                        made[t], schema=table(t).ARROW_SCHEMA))
        finally:
            for w in writers.values():
                w.close()

    # the largest first
    makers = sorted(kept, key=lambda m: -table(m).rows(scale))
    jobs = [(table(m), kept[m], k) for m in makers for k in range(files)]
    with ThreadPoolExecutor(max_workers=max(len(jobs), 1)) as pool:
        for done in [pool.submit(write_file, *job) for job in jobs]:
            done.result()
