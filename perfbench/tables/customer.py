"""TPC-H ``customer``: 150,000 rows a scale factor."""

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

import datagen as dg

SEED_ID = 1
PRIMARY_KEY = "c_custkey"
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]

ARROW_SCHEMA = pa.schema([
    ("c_custkey", pa.int64()), ("c_name", pa.string()),
    ("c_address", pa.string()), ("c_nationkey", pa.int64()),
    ("c_phone", pa.string()), ("c_acctbal", pa.float64()),
    ("c_mktsegment", pa.string()), ("c_comment", pa.string())])


def program_schema():
    from ballista_tpu import Decimal, Int64, Utf8, schema

    return schema(
        ("c_custkey", Int64), ("c_name", Utf8), ("c_address", Utf8),
        ("c_nationkey", Int64), ("c_phone", Utf8), ("c_acctbal", Decimal(2)),
        ("c_mktsegment", Utf8), ("c_comment", Utf8))


def rows(scale: float) -> int:
    return max(int(150_000 * scale), 10)


def _phones(rng, n) -> pa.Array:
    # country prefix 10-34 like dbgen (q22 reads the 2-digit country code)
    return pc.binary_join_element_wise(
        pc.cast(pa.array(rng.integers(10, 35, n)), pa.string()),
        pc.cast(pa.array(rng.integers(10**6, 10**7, n)), pa.string()), "-")


def chunk(rng, lo, hi, scale):
    key = np.arange(lo + 1, hi + 1)
    m = hi - lo
    return {"customer": [
        pa.array(key), dg.tagged("Customer#", key),
        dg.tagged("Addr C", rng.integers(0, 10**6, m)),
        pa.array(rng.integers(0, 25, m)), _phones(rng, m),
        pa.array(dg.money(rng, m, -999.99, 9999.99)),
        dg.strings(rng.integers(0, len(SEGMENTS), m), SEGMENTS),
        dg.comments(rng, m)]}
