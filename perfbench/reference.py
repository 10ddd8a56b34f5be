"""The plain reference's shared part, and the comparison that decides
``correct``.

A query's plain reference is a file of its own, ``queries/<query>.py`` with
``reference(data_dir, precision)``, found by name (``query``). It is
independent of the program: it imports nothing of ``ballista_tpu`` and reads
only the data files. The semantics are those of the repo's pandas oracle
(``benchmarks/tpch/oracle.py``) and of ``queries/*.sql``; what differs is the
arithmetic. The schema's money columns are DECIMAL(2), so at
``precision="exact"`` they are taken as whole cents in int64 and every
product and sum is exact; only the last division makes a float. At
``precision="float32"`` the same columns and every aggregate are float32:
that is the CONTROL, the next precision down that a later PR could be
tempted by, and ``compare`` has to fail it (``perfbench/control.py``).
"""

from __future__ import annotations

import os

import numpy as np
import pandas as pd
import pyarrow.parquet as pq

import byname

PRECISIONS = ("exact", "float32")


def query(name: str):
    """``queries/<name>.py``'s ``reference(data_dir, precision)``."""
    return byname.load("queries", name).reference


def D(s: str) -> int:
    """A date as days since 1970, as ``load`` gives date columns."""
    return int(np.datetime64(s, "D").astype(np.int64))


def load(data_dir: str, table: str, columns) -> dict:
    """Columns of one table as numpy arrays (dates as days since 1970)."""
    base = os.path.join(data_dir, table)
    files = sorted(os.path.join(base, f) for f in os.listdir(base)
                   if f.endswith(".parquet"))
    t = pq.ParquetDataset(files).read(columns=list(columns))
    out = {}
    for name in columns:
        col = t.column(name).combine_chunks()
        if str(col.type) == "date32[day]":
            out[name] = col.cast("int32").to_numpy().astype(np.int64)
        elif str(col.type) in ("string", "large_string"):
            out[name] = col.dictionary_encode()
        else:
            out[name] = col.to_numpy()
    return out


class Money:
    """DECIMAL(2) arithmetic at one of the two precisions. ``col`` takes a
    float64 column of the files; ``one`` is 1.00; ``value`` turns a product
    of ``factors`` money values into float64 units."""

    def __init__(self, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"precision is one of {PRECISIONS}")
        self.exact = precision == "exact"

    def col(self, a):
        if self.exact:
            return np.rint(a * 100.0).astype(np.int64)
        return a.astype(np.float32)

    @property
    def one(self):
        return np.int64(100) if self.exact else np.float32(1.0)

    def total(self, a):
        # float32: numpy's pairwise sum with a float32 accumulator
        return a.sum(dtype=np.int64 if self.exact else np.float32)

    def value(self, total, factors: int) -> float:
        return float(total) / (100.0 ** factors if self.exact else 1.0)


def compare(got: pd.DataFrame, want: pd.DataFrame,
            quotient_columns=()) -> dict:
    """The three numbers an answer is held to, each with a limit of its own
    in the query's file. ``exact_mismatches``: cells of the non-float columns
    (keys, counts, dates, strings) that differ, row by row in the answer's
    own order, plus 1 for a wrong shape or column list. ``sum_rel_gap``: over
    the float columns that hold exact decimal sums, the largest |got - want|
    as a share of |want|. ``quotient_abs_gap``: over ``quotient_columns``
    (averages and ratios, which the program gives as DECIMAL(6)), the largest
    |got - want|."""
    if list(got.columns) != list(want.columns) or len(got) != len(want):
        return {"exact_mismatches": 1, "sum_rel_gap": 1.0,
                "quotient_abs_gap": 1.0}
    out = {"exact_mismatches": 0, "sum_rel_gap": 0.0, "quotient_abs_gap": 0.0}
    for name in want.columns:
        w, g = want[name].to_numpy(), got[name].to_numpy()
        if w.dtype.kind == "f":
            gap = np.nan_to_num(np.abs(g.astype(np.float64) - w), nan=np.inf)
            if name in quotient_columns:
                out["quotient_abs_gap"] = max(out["quotient_abs_gap"],
                                              float(gap.max()))
            else:
                rel = gap / np.maximum(np.abs(w), 1e-300)
                out["sum_rel_gap"] = max(out["sum_rel_gap"], float(rel.max()))
        elif w.dtype.kind == "M":
            out["exact_mismatches"] += int(
                (g.astype("datetime64[D]") != w.astype("datetime64[D]")).sum())
        elif w.dtype.kind in "iu":
            out["exact_mismatches"] += int((g.astype(np.int64) != w).sum())
        else:
            out["exact_mismatches"] += int(
                (g.astype(str) != w.astype(str)).sum())
    return out
