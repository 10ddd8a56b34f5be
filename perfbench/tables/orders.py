"""TPC-H ``orders``: 10 a customer, sparse keys, none for custkey % 3 == 0;
its chunks also make ``lineitem`` (``tables/lineitem.py``), whose attributes
derive from the chunk's own orders only."""

import numpy as np
import pyarrow as pa

import datagen as dg

SEED_ID = 3
PRIMARY_KEY = "o_orderkey"
CUSTOMER, PART = dg.table("customer"), dg.table("part")

START = np.datetime64("1992-01-01", "D")
END_ORDER = np.datetime64("1998-08-02", "D")
CUTOFF = np.datetime64("1995-06-17", "D")  # returnflag/linestatus boundary
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
INSTRUCTIONS = ["COLLECT COD", "DELIVER IN PERSON", "NONE", "TAKE BACK RETURN"]
CLERKS = [f"Clerk#{k}" for k in range(1, 1000)]

ARROW_SCHEMA = pa.schema([
    ("o_orderkey", pa.int64()), ("o_custkey", pa.int64()),
    ("o_orderstatus", pa.string()), ("o_totalprice", pa.float64()),
    ("o_orderdate", pa.date32()), ("o_orderpriority", pa.string()),
    ("o_clerk", pa.string()), ("o_shippriority", pa.int32()),
    ("o_comment", pa.string())])


def program_schema():
    from ballista_tpu import Date32, Decimal, Int32, Int64, Utf8, schema

    return schema(
        ("o_orderkey", Int64), ("o_custkey", Int64), ("o_orderstatus", Utf8),
        ("o_totalprice", Decimal(2)), ("o_orderdate", Date32),
        ("o_orderpriority", Utf8), ("o_clerk", Utf8),
        ("o_shippriority", Int32), ("o_comment", Utf8))


def rows(scale: float) -> int:
    return CUSTOMER.rows(scale) * 10


def _suppliers(scale: float) -> int:
    return max(int(10_000 * scale), 5)


def chunk(rng, lo, hi, scale):
    n = hi - lo
    n_cust, n_part = CUSTOMER.rows(scale), PART.rows(scale)
    n_supp = _suppliers(scale)
    okey = (np.arange(lo, hi) + 1) * 4 - 3  # sparse keys like dbgen
    # dbgen gives no orders to custkey % 3 == 0: draw uniformly over the
    # others through j -> j + (j-1)//2, the j-th integer not divisible by 3
    j = rng.integers(1, n_cust - n_cust // 3 + 1, n)
    o_cust = j + (j - 1) // 2
    span = int((END_ORDER - START) / np.timedelta64(1, "D"))
    o_date = START + rng.integers(0, span, n).astype("timedelta64[D]")
    orders = [
        pa.array(okey), pa.array(o_cust),
        dg.strings(rng.choice(3, n, p=[0.49, 0.49, 0.02]), ["O", "F", "P"]),
        pa.array(dg.money(rng, n, 1000.0, 400000.0)), dg.dates(o_date),
        dg.strings(rng.integers(0, len(PRIORITIES), n), PRIORITIES),
        dg.strings(rng.integers(0, len(CLERKS), n), CLERKS),
        pa.array(np.zeros(n, dtype=np.int32)), dg.comments(rng, n)]

    per = rng.integers(1, 8, n)
    l_okey = np.repeat(okey, per)
    l_odate = np.repeat(o_date, per)
    m = len(l_okey)
    l_pkey = rng.integers(1, n_part + 1, m)
    l_skey = ((l_pkey - 1 + rng.integers(0, 4, m) * (n_supp // 4 + 1))
              % n_supp) + 1
    starts = np.cumsum(per) - per
    l_lnum = (np.arange(m) - np.repeat(starts, per) + 1).astype(np.int32)
    qty = rng.integers(1, 51, m)
    eprice = np.round(qty * PART.retail_price(l_pkey), 2)
    disc = rng.integers(0, 11, m) / 100.0
    tax = rng.integers(0, 9, m) / 100.0
    sdate = l_odate + rng.integers(1, 122, m).astype("timedelta64[D]")
    cdate = l_odate + rng.integers(30, 91, m).astype("timedelta64[D]")
    rdate = sdate + rng.integers(1, 31, m).astype("timedelta64[D]")
    # R or A where the receipt is before the cutoff, else N
    rflag = np.where(rdate <= CUTOFF, rng.integers(0, 2, m), 2)
    lstatus = (sdate > CUTOFF).astype(np.int32)
    lineitem = [
        pa.array(l_okey), pa.array(l_pkey), pa.array(l_skey),
        pa.array(l_lnum), pa.array(qty.astype(np.float64)),
        pa.array(eprice), pa.array(disc), pa.array(tax),
        dg.strings(rflag, ["R", "A", "N"]), dg.strings(lstatus, ["F", "O"]),
        dg.dates(sdate), dg.dates(cdate), dg.dates(rdate),
        dg.strings(rng.integers(0, len(INSTRUCTIONS), m), INSTRUCTIONS),
        dg.strings(rng.integers(0, len(SHIPMODES), m), SHIPMODES),
        dg.comments(rng, m)]
    return {"orders": orders, "lineitem": lineitem}
