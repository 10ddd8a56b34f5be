"""TPC-H ``lineitem``: 1 to 7 rows an order, drawn with their order in
``tables/orders.py``'s chunks (so every lineitem has its order)."""

import pyarrow as pa

MADE_BY = "orders"
PRIMARY_KEY = None

ARROW_SCHEMA = pa.schema([
    ("l_orderkey", pa.int64()), ("l_partkey", pa.int64()),
    ("l_suppkey", pa.int64()), ("l_linenumber", pa.int32()),
    ("l_quantity", pa.float64()), ("l_extendedprice", pa.float64()),
    ("l_discount", pa.float64()), ("l_tax", pa.float64()),
    ("l_returnflag", pa.string()), ("l_linestatus", pa.string()),
    ("l_shipdate", pa.date32()), ("l_commitdate", pa.date32()),
    ("l_receiptdate", pa.date32()), ("l_shipinstruct", pa.string()),
    ("l_shipmode", pa.string()), ("l_comment", pa.string())])


def program_schema():
    from ballista_tpu import Date32, Decimal, Int32, Int64, Utf8, schema

    money = Decimal(2)
    return schema(
        ("l_orderkey", Int64), ("l_partkey", Int64), ("l_suppkey", Int64),
        ("l_linenumber", Int32), ("l_quantity", money),
        ("l_extendedprice", money), ("l_discount", money), ("l_tax", money),
        ("l_returnflag", Utf8), ("l_linestatus", Utf8),
        ("l_shipdate", Date32), ("l_commitdate", Date32),
        ("l_receiptdate", Date32), ("l_shipinstruct", Utf8),
        ("l_shipmode", Utf8), ("l_comment", Utf8))
