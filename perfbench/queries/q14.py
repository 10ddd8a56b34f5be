"""The plain reference of TPC-H Q14 (promotion effect, 1995-09-01):
lineitem x part, one ratio."""

import numpy as np
import pandas as pd

from reference import D, Money, load


def reference(data_dir: str, precision: str = "exact") -> pd.DataFrame:
    m = Money(precision)
    p = load(data_dir, "part", ["p_partkey", "p_type"])
    ptype = p["p_type"]
    promo_codes = np.array([s.startswith("PROMO")
                            for s in ptype.dictionary.to_pylist()])
    promo_parts = p["p_partkey"][promo_codes[ptype.indices.to_numpy()]]
    l = load(data_dir, "lineitem", [
        "l_partkey", "l_extendedprice", "l_discount", "l_shipdate"])
    keep = ((l["l_shipdate"] >= D("1995-09-01"))
            & (l["l_shipdate"] < D("1995-10-01"))
            & np.isin(l["l_partkey"], p["p_partkey"]))
    revenue = m.col(l["l_extendedprice"][keep]) * (
        m.one - m.col(l["l_discount"][keep]))
    promo = np.isin(l["l_partkey"][keep], promo_parts)
    total = m.value(m.total(revenue), 2)
    return pd.DataFrame({"promo_revenue": [
        100.0 * m.value(m.total(revenue[promo]), 2) / total]})
