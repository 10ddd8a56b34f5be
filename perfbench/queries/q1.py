"""The plain reference of TPC-H Q1 (pricing summary report, DELTA = 90):
lineitem grouped by returnflag and linestatus."""

import numpy as np
import pandas as pd

from reference import D, Money, load


def reference(data_dir: str, precision: str = "exact") -> pd.DataFrame:
    m = Money(precision)
    t = load(data_dir, "lineitem", [
        "l_quantity", "l_extendedprice", "l_discount", "l_tax",
        "l_returnflag", "l_linestatus", "l_shipdate"])
    keep = t["l_shipdate"] <= D("1998-12-01") - 90
    flag, status = t["l_returnflag"], t["l_linestatus"]
    nstat = len(status.dictionary)
    group = (flag.indices.to_numpy().astype(np.int64) * nstat
             + status.indices.to_numpy())[keep]
    qty, price, disc, tax = (m.col(t[c][keep]) for c in (
        "l_quantity", "l_extendedprice", "l_discount", "l_tax"))
    disc_price = price * (m.one - disc)
    charge = disc_price * (m.one + tax)
    rows = []
    for g in np.unique(group):
        sel = group == g
        n = int(sel.sum())
        sums = {"qty": m.value(m.total(qty[sel]), 1),
                "price": m.value(m.total(price[sel]), 1),
                "disc": m.value(m.total(disc[sel]), 1)}
        rows.append({
            "l_returnflag": flag.dictionary[int(g) // nstat].as_py(),
            "l_linestatus": status.dictionary[int(g) % nstat].as_py(),
            "sum_qty": sums["qty"], "sum_base_price": sums["price"],
            "sum_disc_price": m.value(m.total(disc_price[sel]), 2),
            "sum_charge": m.value(m.total(charge[sel]), 3),
            "avg_qty": sums["qty"] / n, "avg_price": sums["price"] / n,
            "avg_disc": sums["disc"] / n, "count_order": n})
    return (pd.DataFrame(rows)
            .sort_values(["l_returnflag", "l_linestatus"])
            .reset_index(drop=True))
