"""Independent pandas implementations of the TPC-H queries, used as the
correctness oracle for the engine (golden results; the reference eyeballs a
known q1 table, rust/benchmarks/tpch/README.md:70-84 — we assert
programmatically instead)."""

from __future__ import annotations

import os

import numpy as np
import pandas as pd

from .schema_def import TPCH_SCHEMAS

_D = lambda s: np.datetime64(s, "D")


def load_tables(data_dir: str, only=None) -> dict:
    """``only``: subset of table names to load (large scale factors:
    loading all 8 tables into pandas costs tens of GB of RAM)."""
    out = {}
    for name, sch in TPCH_SCHEMAS.items():
        if only is not None and name not in only:
            continue
        base = os.path.join(data_dir, name)
        files = (
            sorted(
                os.path.join(base, f) for f in os.listdir(base)
                if f.endswith(".tbl")
            )
            if os.path.isdir(base)
            else [base + ".tbl"]
        )
        names = list(sch.names()) + ["__t"]
        parts = [
            pd.read_csv(f, sep="|", header=None, names=names,
                        usecols=range(len(sch)))
            for f in files
        ]
        df = pd.concat(parts, ignore_index=True)
        for f_ in sch.fields:
            if f_.dtype.kind == "date32":
                df[f_.name] = pd.to_datetime(df[f_.name]).values.astype(
                    "datetime64[D]"
                )
        out[name] = df
    return out


def q1(t):
    l = t["lineitem"]
    d = l[l.l_shipdate <= _D("1998-09-02")]
    g = d.groupby(["l_returnflag", "l_linestatus"])

    def agg(x):
        disc = x.l_extendedprice * (1 - x.l_discount)
        return pd.Series({
            "sum_qty": x.l_quantity.sum(),
            "sum_base_price": x.l_extendedprice.sum(),
            "sum_disc_price": disc.sum(),
            "sum_charge": (disc * (1 + x.l_tax)).sum(),
            "avg_qty": x.l_quantity.mean(),
            "avg_price": x.l_extendedprice.mean(),
            "avg_disc": x.l_discount.mean(),
            "count_order": len(x),
        })

    return (
        g.apply(agg, include_groups=False)
        .reset_index()
        .sort_values(["l_returnflag", "l_linestatus"])
        .reset_index(drop=True)
    )


def q3(t):
    c = t["customer"]; o = t["orders"]; l = t["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < _D("1995-03-15")]
    l = l[l.l_shipdate > _D("1995-03-15")]
    j = l.merge(o, left_on="l_orderkey", right_on="o_orderkey").merge(
        c, left_on="o_custkey", right_on="c_custkey"
    )
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    out = (
        j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])["revenue"]
        .sum()
        .reset_index()[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
        .sort_values(["revenue", "o_orderdate"], ascending=[False, True])
        .head(10)
        .reset_index(drop=True)
    )
    return out


def q5(t):
    c, o, l = t["customer"], t["orders"], t["lineitem"]
    s, n, r = t["supplier"], t["nation"], t["region"]
    r = r[r.r_name == "ASIA"]
    o = o[(o.o_orderdate >= _D("1994-01-01")) & (o.o_orderdate < _D("1995-01-01"))]
    j = (
        l.merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
    )
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(n, left_on="s_nationkey", right_on="n_nationkey").merge(
        r, left_on="n_regionkey", right_on="r_regionkey"
    )
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    return (
        j.groupby("n_name")["revenue"].sum().reset_index()
        .sort_values("revenue", ascending=False).reset_index(drop=True)
    )


def q6(t):
    l = t["lineitem"]
    d = l[
        (l.l_shipdate >= _D("1994-01-01")) & (l.l_shipdate < _D("1995-01-01"))
        & (l.l_discount >= 0.05) & (l.l_discount <= 0.07) & (l.l_quantity < 24)
    ]
    return pd.DataFrame({"revenue": [(d.l_extendedprice * d.l_discount).sum()]})


def q10(t):
    c, o, l, n = t["customer"], t["orders"], t["lineitem"], t["nation"]
    o = o[(o.o_orderdate >= _D("1993-10-01")) & (o.o_orderdate < _D("1994-01-01"))]
    l = l[l.l_returnflag == "R"]
    j = (
        l.merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n, left_on="c_nationkey", right_on="n_nationkey")
    )
    j["revenue"] = j.l_extendedprice * (1 - j.l_discount)
    out = (
        j.groupby(["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name",
                   "c_address", "c_comment"])["revenue"].sum().reset_index()
    )
    out = out[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
               "c_address", "c_phone", "c_comment"]]
    return (
        out.sort_values("revenue", ascending=False).head(20).reset_index(drop=True)
    )


def q12(t):
    o, l = t["orders"], t["lineitem"]
    d = l[
        l.l_shipmode.isin(["MAIL", "SHIP"])
        & (l.l_commitdate < l.l_receiptdate)
        & (l.l_shipdate < l.l_commitdate)
        & (l.l_receiptdate >= _D("1994-01-01"))
        & (l.l_receiptdate < _D("1995-01-01"))
    ]
    j = d.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    high = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    out = (
        j.assign(high=high.astype(int), low=(~high).astype(int))
        .groupby("l_shipmode")[["high", "low"]].sum().reset_index()
        .rename(columns={"high": "high_line_count", "low": "low_line_count"})
        .sort_values("l_shipmode").reset_index(drop=True)
    )
    return out


def q4(t):
    o, l = t["orders"], t["lineitem"]
    o = o[(o.o_orderdate >= _D("1993-07-01")) & (o.o_orderdate < _D("1993-10-01"))]
    late = l[l.l_commitdate < l.l_receiptdate].l_orderkey.unique()
    d = o[o.o_orderkey.isin(late)]
    return (
        d.groupby("o_orderpriority").size().reset_index(name="order_count")
        .sort_values("o_orderpriority").reset_index(drop=True)
    )


def q7(t):
    s, l, o, c, n = (t["supplier"], t["lineitem"], t["orders"], t["customer"],
                     t["nation"])
    l = l[(l.l_shipdate >= _D("1995-01-01")) & (l.l_shipdate <= _D("1996-12-31"))]
    j = (
        l.merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n.add_prefix("n1_"), left_on="s_nationkey",
               right_on="n1_n_nationkey")
        .merge(n.add_prefix("n2_"), left_on="c_nationkey",
               right_on="n2_n_nationkey")
    )
    j = j[
        ((j.n1_n_name == "FRANCE") & (j.n2_n_name == "GERMANY"))
        | ((j.n1_n_name == "GERMANY") & (j.n2_n_name == "FRANCE"))
    ]
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    j["l_year"] = pd.to_datetime(j.l_shipdate).dt.year
    out = (
        j.groupby([j.n1_n_name.rename("supp_nation"),
                   j.n2_n_name.rename("cust_nation"), "l_year"])["volume"]
        .sum().reset_index().rename(columns={"volume": "revenue"})
        .sort_values(["supp_nation", "cust_nation", "l_year"])
        .reset_index(drop=True)
    )
    return out


def q8(t):
    p, s, l, o, c, n, r = (t["part"], t["supplier"], t["lineitem"],
                           t["orders"], t["customer"], t["nation"], t["region"])
    o = o[(o.o_orderdate >= _D("1995-01-01")) & (o.o_orderdate <= _D("1996-12-31"))]
    p = p[p.p_type == "ECONOMY ANODIZED STEEL"]
    j = (
        l.merge(p, left_on="l_partkey", right_on="p_partkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
        .merge(n.add_prefix("n1_"), left_on="c_nationkey",
               right_on="n1_n_nationkey")
        .merge(r, left_on="n1_n_regionkey", right_on="r_regionkey")
        .merge(n.add_prefix("n2_"), left_on="s_nationkey",
               right_on="n2_n_nationkey")
    )
    j = j[j.r_name == "AMERICA"]
    j["o_year"] = pd.to_datetime(j.o_orderdate).dt.year
    j["volume"] = j.l_extendedprice * (1 - j.l_discount)
    j["brazil"] = np.where(j.n2_n_name == "BRAZIL", j.volume, 0.0)
    out = (
        j.groupby("o_year").agg(b=("brazil", "sum"), v=("volume", "sum"))
        .reset_index()
    )
    out["mkt_share"] = out.b / out.v
    return out[["o_year", "mkt_share"]].sort_values("o_year").reset_index(drop=True)


def q9(t):
    p, s, l, ps, o, n = (t["part"], t["supplier"], t["lineitem"],
                         t["partsupp"], t["orders"], t["nation"])
    p = p[p.p_name.str.contains("green")]
    j = (
        l.merge(p, left_on="l_partkey", right_on="p_partkey")
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
        .merge(ps, left_on=["l_partkey", "l_suppkey"],
               right_on=["ps_partkey", "ps_suppkey"])
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    j["o_year"] = pd.to_datetime(j.o_orderdate).dt.year
    j["amount"] = (j.l_extendedprice * (1 - j.l_discount)
                   - j.ps_supplycost * j.l_quantity)
    return (
        j.groupby([j.n_name.rename("nation"), "o_year"])["amount"].sum()
        .reset_index().rename(columns={"amount": "sum_profit"})
        .sort_values(["nation", "o_year"], ascending=[True, False])
        .reset_index(drop=True)
    )


def q11(t):
    ps, s, n = t["partsupp"], t["supplier"], t["nation"]
    j = ps.merge(s, left_on="ps_suppkey", right_on="s_suppkey").merge(
        n, left_on="s_nationkey", right_on="n_nationkey"
    )
    j = j[j.n_name == "GERMANY"]
    j["value"] = j.ps_supplycost * j.ps_availqty
    total = j.value.sum() * 0.0001
    out = j.groupby("ps_partkey")["value"].sum().reset_index()
    out = out[out.value > total]
    return out.sort_values("value", ascending=False).reset_index(drop=True)


def q13(t):
    c, o = t["customer"], t["orders"]
    o = o[~o.o_comment.str.contains("special.*requests")]
    counts = (
        c.merge(o, left_on="c_custkey", right_on="o_custkey", how="left")
        .groupby("c_custkey")["o_orderkey"].count().reset_index(name="c_count")
    )
    return (
        counts.groupby("c_count").size().reset_index(name="custdist")
        .sort_values(["custdist", "c_count"], ascending=[False, False])
        .reset_index(drop=True)
    )


def q14(t):
    l, p = t["lineitem"], t["part"]
    l = l[(l.l_shipdate >= _D("1995-09-01")) & (l.l_shipdate < _D("1995-10-01"))]
    j = l.merge(p, left_on="l_partkey", right_on="p_partkey")
    rev = j.l_extendedprice * (1 - j.l_discount)
    promo = rev.where(j.p_type.str.startswith("PROMO"), 0.0)
    return pd.DataFrame({"promo_revenue": [100.0 * promo.sum() / rev.sum()]})


def q16(t):
    ps, p, s = t["partsupp"], t["part"], t["supplier"]
    bad = s[s.s_comment.str.contains("Customer.*Complaints")].s_suppkey
    d = p[
        (p.p_brand != "Brand#45")
        & ~p.p_type.str.startswith("MEDIUM POLISHED")
        & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])
    ]
    j = ps.merge(d, left_on="ps_partkey", right_on="p_partkey")
    j = j[~j.ps_suppkey.isin(bad)]
    out = (
        j.groupby(["p_brand", "p_type", "p_size"])["ps_suppkey"].nunique()
        .reset_index(name="supplier_cnt")
        .sort_values(["supplier_cnt", "p_brand", "p_type", "p_size"],
                     ascending=[False, True, True, True])
        .reset_index(drop=True)
    )
    return out


def q18(t):
    c, o, l = t["customer"], t["orders"], t["lineitem"]
    big = l.groupby("l_orderkey")["l_quantity"].sum()
    big = big[big > 300].index
    j = (
        l[l.l_orderkey.isin(big)]
        .merge(o, left_on="l_orderkey", right_on="o_orderkey")
        .merge(c, left_on="o_custkey", right_on="c_custkey")
    )
    out = (
        j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"])["l_quantity"].sum()
        .reset_index(name="total_qty")
        .sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True])
        .head(100).reset_index(drop=True)
    )
    return out


def q19(t):
    l, p = t["lineitem"], t["part"]
    j = l.merge(p, left_on="l_partkey", right_on="p_partkey")
    common = j.l_shipmode.isin(["AIR", "AIR REG"]) & (
        j.l_shipinstruct == "DELIVER IN PERSON"
    )
    b1 = (
        (j.p_brand == "Brand#12")
        & j.p_container.isin(["SM CASE", "SM BOX", "SM PACK", "SM PKG"])
        & (j.l_quantity >= 1) & (j.l_quantity <= 11)
        & (j.p_size >= 1) & (j.p_size <= 5)
    )
    b2 = (
        (j.p_brand == "Brand#23")
        & j.p_container.isin(["MED BAG", "MED BOX", "MED PKG", "MED PACK"])
        & (j.l_quantity >= 10) & (j.l_quantity <= 20)
        & (j.p_size >= 1) & (j.p_size <= 10)
    )
    b3 = (
        (j.p_brand == "Brand#34")
        & j.p_container.isin(["LG CASE", "LG BOX", "LG PACK", "LG PKG"])
        & (j.l_quantity >= 20) & (j.l_quantity <= 30)
        & (j.p_size >= 1) & (j.p_size <= 15)
    )
    d = j[common & (b1 | b2 | b3)]
    # SQL: SUM over zero rows is NULL (NaN), not 0
    rev = (d.l_extendedprice * (1 - d.l_discount)).sum() if len(d) else np.nan
    return pd.DataFrame({"revenue": [rev]})


def q22(t):
    c, o = t["customer"], t["orders"]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cc = c[c.c_phone.str[:2].isin(codes)]
    avg_bal = cc[cc.c_acctbal > 0].c_acctbal.mean()
    d = cc[(cc.c_acctbal > avg_bal) & ~cc.c_custkey.isin(o.o_custkey)]
    out = (
        d.assign(cntrycode=d.c_phone.str[:2])
        .groupby("cntrycode")
        .agg(numcust=("c_acctbal", "size"), totacctbal=("c_acctbal", "sum"))
        .reset_index().sort_values("cntrycode").reset_index(drop=True)
    )
    return out


def q2(t):
    p, s, ps, n, r = (t["part"], t["supplier"], t["partsupp"], t["nation"],
                      t["region"])
    europe = (
        ps.merge(s, left_on="ps_suppkey", right_on="s_suppkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
        .merge(r, left_on="n_regionkey", right_on="r_regionkey")
    )
    europe = europe[europe.r_name == "EUROPE"]
    mins = europe.groupby("ps_partkey")["ps_supplycost"].min().reset_index(
        name="min_cost"
    )
    d = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    j = (
        europe.merge(d, left_on="ps_partkey", right_on="p_partkey")
        .merge(mins, on="ps_partkey")
    )
    j = j[j.ps_supplycost == j.min_cost]
    out = j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
             "s_address", "s_phone", "s_comment"]]
    return (
        out.sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                        ascending=[False, True, True, True])
        .head(100).reset_index(drop=True)
    )


def q15(t):
    s, l = t["supplier"], t["lineitem"]
    d = l[(l.l_shipdate >= _D("1996-01-01")) & (l.l_shipdate < _D("1996-04-01"))]
    rev = (
        d.assign(r=d.l_extendedprice * (1 - d.l_discount))
        .groupby("l_suppkey")["r"].sum().reset_index(name="total_revenue")
    )
    top = rev[rev.total_revenue == rev.total_revenue.max()]
    j = s.merge(top, left_on="s_suppkey", right_on="l_suppkey")
    return (
        j[["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]]
        .sort_values("s_suppkey").reset_index(drop=True)
    )


def q17(t):
    l, p = t["lineitem"], t["part"]
    d = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    j = l.merge(d, left_on="l_partkey", right_on="p_partkey")
    avg_qty = l.groupby("l_partkey")["l_quantity"].mean().rename("avg_q")
    j = j.join(avg_qty, on="l_partkey")
    j = j[j.l_quantity < 0.2 * j.avg_q]
    val = j.l_extendedprice.sum() / 7.0 if len(j) else np.nan
    return pd.DataFrame({"avg_yearly": [val]})


def q20(t):
    s, n, ps, p, l = (t["supplier"], t["nation"], t["partsupp"], t["part"],
                      t["lineitem"])
    green = p[p.p_name.str.startswith("green")].p_partkey
    d = l[(l.l_shipdate >= _D("1994-01-01")) & (l.l_shipdate < _D("1995-01-01"))]
    qty = (
        d.groupby(["l_partkey", "l_suppkey"])["l_quantity"].sum()
        .reset_index(name="sumq")
    )
    j = ps[ps.ps_partkey.isin(green)].merge(
        qty, left_on=["ps_partkey", "ps_suppkey"],
        right_on=["l_partkey", "l_suppkey"],
    )
    good = j[j.ps_availqty > 0.5 * j.sumq].ps_suppkey.unique()
    out = s[s.s_suppkey.isin(good)].merge(
        n, left_on="s_nationkey", right_on="n_nationkey"
    )
    out = out[out.n_name == "CANADA"][["s_name", "s_address"]]
    return out.sort_values("s_name").reset_index(drop=True)


def q21(t):
    s_, l, o, n = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    late = l[l.l_receiptdate > l.l_commitdate]
    # per order: distinct suppliers among all / among late lineitems
    nsupp = l.groupby("l_orderkey")["l_suppkey"].nunique()
    nlate = late.groupby("l_orderkey")["l_suppkey"].nunique()
    j = (
        late.merge(o[o.o_orderstatus == "F"], left_on="l_orderkey",
                   right_on="o_orderkey")
        .merge(s_, left_on="l_suppkey", right_on="s_suppkey")
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    )
    j = j[j.n_name == "SAUDI ARABIA"]
    j = j.join(nsupp.rename("nsupp"), on="l_orderkey")
    j = j.join(nlate.rename("nlate"), on="l_orderkey")
    # exists other-supplier lineitem; no other-supplier LATE lineitem
    j = j[(j.nsupp >= 2) & (j.nlate == 1)]
    return (
        j.groupby("s_name").size().reset_index(name="numwait")
        .sort_values(["numwait", "s_name"], ascending=[False, True])
        .head(100).reset_index(drop=True)
    )


ORACLES = {
    "q1": q1, "q2": q2, "q3": q3, "q4": q4, "q5": q5, "q6": q6, "q7": q7,
    "q8": q8, "q9": q9, "q10": q10, "q11": q11, "q12": q12, "q13": q13,
    "q14": q14, "q15": q15, "q16": q16, "q17": q17, "q18": q18, "q19": q19,
    "q20": q20, "q21": q21, "q22": q22,
}


def assert_frames_match(qname: str, got: pd.DataFrame,
                        expected: pd.DataFrame) -> None:
    """The repo's one comparison of an engine result with an oracle
    frame: same columns, same row count, floats to rtol/atol 1e-6,
    everything else exact (dates compared at day precision)."""

    def normalize(df):
        out = df.copy()
        for c in out.columns:
            if out[c].dtype.kind == "M":
                out[c] = out[c].values.astype("datetime64[D]")
        return out.reset_index(drop=True)

    got, expected = normalize(got), normalize(expected)
    # explicit raises, not ``assert``: chip_smoke.py's verdict must not
    # depend on the interpreter running without -O
    if list(got.columns) != list(expected.columns):
        raise AssertionError(f"{qname}: columns {list(got.columns)} vs "
                             f"{list(expected.columns)}")
    if len(got) != len(expected):
        raise AssertionError(f"{qname}: {len(got)} rows vs {len(expected)}")
    for c in expected.columns:
        g, e = got[c], expected[c]
        if e.dtype.kind in "fc":
            np.testing.assert_allclose(
                g.astype(float), e.astype(float), rtol=1e-6, atol=1e-6,
                err_msg=f"{qname}.{c}")
        else:
            np.testing.assert_array_equal(
                g.to_numpy(), e.to_numpy(), err_msg=f"{qname}.{c}")
