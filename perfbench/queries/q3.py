"""The plain reference of TPC-H Q3 (shipping priority: BUILDING,
1995-03-15): customer x orders x lineitem, grouped by order, top 10."""

import numpy as np
import pandas as pd

from reference import D, Money, load


def reference(data_dir: str, precision: str = "exact") -> pd.DataFrame:
    m = Money(precision)
    c = load(data_dir, "customer", ["c_custkey", "c_mktsegment"])
    seg = c["c_mktsegment"]
    building = seg.dictionary.to_pylist().index("BUILDING")
    cust = c["c_custkey"][seg.indices.to_numpy() == building]
    o = load(data_dir, "orders", [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"])
    keep_o = (o["o_orderdate"] < D("1995-03-15")) & np.isin(
        o["o_custkey"], cust)
    orders = pd.DataFrame({
        "l_orderkey": o["o_orderkey"][keep_o],
        "o_orderdate": o["o_orderdate"][keep_o],
        "o_shippriority": o["o_shippriority"][keep_o]})
    l = load(data_dir, "lineitem", [
        "l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"])
    keep_l = (l["l_shipdate"] > D("1995-03-15")) & np.isin(
        l["l_orderkey"], orders["l_orderkey"].to_numpy())
    revenue = m.col(l["l_extendedprice"][keep_l]) * (
        m.one - m.col(l["l_discount"][keep_l]))
    lines = pd.DataFrame({"l_orderkey": l["l_orderkey"][keep_l],
                          "revenue": revenue})
    # pandas keeps int64 and float32 group sums in their own type
    sums = lines.groupby("l_orderkey", sort=False)["revenue"].sum()
    out = orders.merge(sums.reset_index(), on="l_orderkey")
    out["revenue"] = [m.value(v, 2) for v in out["revenue"].to_numpy()]
    out = (out.sort_values(["revenue", "o_orderdate"],
                           ascending=[False, True])
           .head(10).reset_index(drop=True))
    out["o_orderdate"] = out["o_orderdate"].to_numpy().astype(
        "datetime64[D]")
    return out[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
