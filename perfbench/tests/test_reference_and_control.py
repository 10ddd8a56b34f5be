"""The plain reference agrees with a pandas computation in float64, the data
is a function of the seed, and the CONTROL (the reference in float32) fails
the comparison at a size a test can hold."""

import hashlib
import os

import numpy as np
import pandas as pd
import pytest

import control
import datagen
import reference
import run

TABLES = ["customer", "lineitem", "orders", "part"]
BIG_SEED = 2**31 + 977


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("data"))
    datagen.generate(d, 0.02, TABLES, 4, BIG_SEED)
    return d


def _digest(d):
    h = hashlib.sha256()
    for t in TABLES:
        for f in sorted(os.listdir(os.path.join(d, t))):
            with open(os.path.join(d, t, f), "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_same_seed_same_bytes_other_seed_other_bytes(data_dir, tmp_path):
    datagen.generate(str(tmp_path / "a"), 0.02, TABLES, 4, BIG_SEED)
    datagen.generate(str(tmp_path / "b"), 0.02, TABLES, 4, BIG_SEED + 1)
    assert _digest(str(tmp_path / "a")) == _digest(data_dir)
    assert _digest(str(tmp_path / "b")) != _digest(data_dir)
    # a cell that reads lineitem alone gets the same lineitem
    datagen.generate(str(tmp_path / "c"), 0.02, ["lineitem"], 4, BIG_SEED)
    one = pd.read_parquet(tmp_path / "c" / "lineitem")
    assert one.equals(pd.read_parquet(os.path.join(data_dir, "lineitem")))
    assert not os.path.exists(tmp_path / "c" / "orders")


def test_distributions_the_queries_rely_on(data_dir):
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"))
    orders = pd.read_parquet(os.path.join(data_dir, "orders"))
    assert len(orders) == 30_000 and 3.8 < len(li) / len(orders) < 4.2
    assert set(li.l_returnflag) == {"R", "A", "N"}
    assert set(li.l_linestatus) == {"O", "F"}
    assert not (orders.o_custkey % 3 == 0).any()
    assert li.l_orderkey.isin(orders.o_orderkey).all()
    cents = li.l_extendedprice * 100
    assert np.abs(cents - cents.round()).max() < 1e-6
    assert li.l_discount.between(0, 0.10).all() and li.l_tax.max() == 0.08


def test_reference_agrees_with_pandas_in_float64(data_dir):
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"))
    d = li[li.l_shipdate <= pd.Timestamp("1998-09-02").date()]
    want = d.assign(dp=d.l_extendedprice * (1 - d.l_discount)).groupby(
        ["l_returnflag", "l_linestatus"]).agg(
            sum_disc_price=("dp", "sum"), avg_qty=("l_quantity", "mean"),
            count_order=("dp", "size")).reset_index()
    got = reference.query("q1")(data_dir)
    assert list(got.count_order) == list(want.count_order)
    np.testing.assert_allclose(got.sum_disc_price, want.sum_disc_price,
                               rtol=1e-9)
    np.testing.assert_allclose(got.avg_qty, want.avg_qty, rtol=1e-9)
    part = pd.read_parquet(os.path.join(data_dir, "part"))
    j = li[(li.l_shipdate >= pd.Timestamp("1995-09-01").date())
           & (li.l_shipdate < pd.Timestamp("1995-10-01").date())].merge(
               part, left_on="l_partkey", right_on="p_partkey")
    rev = j.l_extendedprice * (1 - j.l_discount)
    promo = 100 * rev[j.p_type.str.startswith("PROMO")].sum() / rev.sum()
    assert reference.query("q14")(data_dir).promo_revenue[0] == pytest.approx(
        promo, rel=1e-9)
    assert len(reference.query("q3")(data_dir)) == 10


def test_compare_counts_every_kind_of_difference(data_dir):
    want = reference.query("q3")(data_dir)
    spec = run.read_json(run.HERE, "queries", "q3.json")
    same = reference.compare(want.copy(), want, spec["quotient_columns"])
    assert same == {"exact_mismatches": 0, "sum_rel_gap": 0.0,
                    "quotient_abs_gap": 0.0}
    cent = want.copy()
    cent.loc[3, "revenue"] += 0.01
    assert reference.compare(cent, want)["sum_rel_gap"] > 1e-12
    swapped = want.iloc[[1, 0] + list(range(2, 10))].reset_index(drop=True)
    assert reference.compare(swapped, want)["exact_mismatches"] >= 2
    assert reference.compare(want.head(9), want)["exact_mismatches"] == 1


def test_a_row_left_out_of_q14_is_over_its_limit(data_dir, tmp_path):
    """q14's ratio is the number float32 hardly moves (the program divides
    in float32 itself), so its limit is held against a wrong ratio: the
    month's smallest row left out. At SF3 that is 8.8e-6 (PERF.md section
    2); here, at SF0.02 with 150 times fewer rows, it is far more."""
    import pyarrow.parquet as pq

    spec = run.read_json(run.HERE, "queries", "q14.json")
    want = reference.query("q14")(data_dir)
    li = pd.read_parquet(os.path.join(data_dir, "lineitem"))
    month = li[(li.l_shipdate >= pd.Timestamp("1995-09-01").date())
               & (li.l_shipdate < pd.Timestamp("1995-10-01").date())]
    drop = (month.l_extendedprice * (1 - month.l_discount)).idxmin()
    os.makedirs(tmp_path / "lineitem")
    schema = pq.read_schema(os.path.join(data_dir, "lineitem",
                                         "part-0.parquet"))
    li.drop(index=drop).to_parquet(tmp_path / "lineitem" / "part-0.parquet",
                                   schema=schema, index=False)
    os.symlink(os.path.join(data_dir, "part"), tmp_path / "part")
    got = reference.compare(reference.query("q14")(str(tmp_path)), want,
                            spec["quotient_columns"])
    assert got["quotient_abs_gap"] > 10 * spec["limits"]["quotient_abs_gap"]
    assert got["sum_rel_gap"] == 0 and got["exact_mismatches"] == 0


@pytest.mark.parametrize("cell", ["served-scanagg", "standalone-join",
                                  "served-join", "standalone-scanagg"])
def test_float32_control_fails_the_comparison(data_dir, cell):
    result = control.control(run.find_cell(cell), data_dir)
    assert result["caught"], result
    over = {k for q in result["queries"].values() for k in q["over"]}
    assert "sum_rel_gap" in over
