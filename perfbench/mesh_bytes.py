"""The bytes a query's hash-partitioned joins have to move BETWEEN chips:
for every side of such a join, the rows that pass the side's filters times
the widths of the columns the join needs (at the width a column has on the
device, ``bytes_model.width``), times the share of rows whose hash sends
them to another chip, (chips - 1) / chips for a uniform hash. Reckoned from
the data and the query text alone, so that a share of the interconnect's
peak reads the same work whatever implements the exchange; kept with the
benchmark so that no later PR can change what the share is a share of.

``EXCHANGED`` names, per query, the sides of the joins that the deployment
``tpch-mesh4-served`` guarantees to exchange on the mesh: the joins whose
two sides are both too large to be merged onto every chip. Q3's is orders
x lineitem (its customer x orders join has a small build side, and the
result of it IS the orders side here). Q14's one join has ``part`` for its
build side, which every plan broadcasts: nothing of Q14 has to cross.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np
import pyarrow.parquet as pq

import bytes_model
from reference import D, load


def _q3_lineitem(data_dir):
    l = load(data_dir, "lineitem", ["l_shipdate"])
    return int(np.count_nonzero(l["l_shipdate"] > D("1995-03-15")))


def _q3_orders(data_dir):
    c = load(data_dir, "customer", ["c_custkey", "c_mktsegment"])
    seg = c["c_mktsegment"]
    building = seg.dictionary.to_pylist().index("BUILDING")
    cust = c["c_custkey"][seg.indices.to_numpy() == building]
    o = load(data_dir, "orders", ["o_custkey", "o_orderdate"])
    return int(np.count_nonzero((o["o_orderdate"] < D("1995-03-15"))
                                & np.isin(o["o_custkey"], cust)))


# query -> [(side, table, the columns the join needs of it, rows that pass)]
EXCHANGED = {
    "q3": [("lineitem", "lineitem",
            ["l_orderkey", "l_extendedprice", "l_discount"], _q3_lineitem),
           ("orders", "orders",
            ["o_orderkey", "o_orderdate", "o_shippriority"], _q3_orders)],
    "q14": [],
}


def exchanges(query: str) -> bool:
    """Whether the deployment guarantees a mesh exchange in this query."""
    return bool(EXCHANGED.get(query))


def sides(query: str, data_dir: str) -> list:
    """``[{"side", "rows", "row_bytes"}]`` for the query's exchanged joins."""
    out = []
    for side, table, columns, rows in EXCHANGED.get(query, []):
        base = os.path.join(data_dir, table)
        first = sorted(f for f in os.listdir(base) if f.endswith(".parquet"))[0]
        schema = pq.ParquetFile(os.path.join(base, first)).schema_arrow
        out.append({"side": side, "rows": rows(data_dir),
                    "row_bytes": sum(bytes_model.width(schema.field(c).type)
                                     for c in columns)})
    return out


def rows_exchanged(query: str, data_dir: str) -> int:
    return sum(s["rows"] for s in sides(query, data_dir))


def crossing_bytes(query: str, data_dir: str, chips: int) -> float:
    """Bytes of one execution that have to leave the chip they start on."""
    return sum(s["rows"] * s["row_bytes"] for s in sides(query, data_dir)) \
        * (chips - 1) / chips


def data_dir_of(obs) -> str:
    """The run's data directory: ``obs["data_dir"]`` where a caller gives
    it, else found as the harness found it, from the cell's configuration
    and the command line's ``--seed`` (and ``--rehearse``)."""
    if obs.get("data_dir"):
        return obs["data_dir"]
    import run

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rehearse", action="store_true")
    args, _ = ap.parse_known_args(sys.argv[1:])
    return run.cell_data(obs["cell"], args.seed, args.rehearse)[0]
