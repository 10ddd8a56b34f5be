"""The plain reference of TPC-H Q6 (forecasting revenue change: 1994, 0.06,
24): one sum over lineitem."""

import numpy as np
import pandas as pd

from reference import D, Money, load


def reference(data_dir: str, precision: str = "exact") -> pd.DataFrame:
    m = Money(precision)
    t = load(data_dir, "lineitem", [
        "l_quantity", "l_extendedprice", "l_discount", "l_shipdate"])
    # the predicates are on DECIMAL(2) values: taken in whole cents at
    # either precision, so that both select the same rows
    disc_c = np.rint(t["l_discount"] * 100.0).astype(np.int64)
    qty_c = np.rint(t["l_quantity"] * 100.0).astype(np.int64)
    keep = ((t["l_shipdate"] >= D("1994-01-01"))
            & (t["l_shipdate"] < D("1995-01-01"))
            & (disc_c >= 5) & (disc_c <= 7) & (qty_c < 2400))
    revenue = m.col(t["l_extendedprice"][keep]) * m.col(t["l_discount"][keep])
    return pd.DataFrame({"revenue": [m.value(m.total(revenue), 2)]})
