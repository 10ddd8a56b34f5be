"""Multithreaded native scanner: N workers parse disjoint byte sub-ranges
and merge (utf8 codes remapped onto a union dictionary), so results must
be byte-identical to the single-threaded parse. Reference role: DataFusion
reads partitions concurrently on tokio workers; here one big file fans out
across threads inside the C++ scanner itself.
"""

import os

import numpy as np
import pytest

from ballista_tpu.io import native
from ballista_tpu import schema, Int64, Utf8, Decimal, Date32


pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native scanner not built")


@pytest.fixture(autouse=True)
def tiny_thread_floor(monkeypatch):
    # let small test files still split across threads
    monkeypatch.setenv("TBLSCAN_MIN_THREAD_BYTES", "64")


def _write(tmp_path, rows=5000):
    p = tmp_path / "t.tbl"
    lines = []
    for i in range(rows):
        d = f"1995-{(i % 12) + 1:02d}-{(i % 28) + 1:02d}"
        val = "" if i % 17 == 0 else str(i)  # NULLs cross span boundaries
        lines.append(f"{val}|key{i % 41}|{i}.{i % 100:02d}|{d}|\n")
    p.write_text("".join(lines))
    return str(p)


SCHEMA = schema(("a", Int64), ("c", Utf8), ("d", Decimal(2)),
                ("dt", Date32))


def test_mt_equals_single_thread(tmp_path):
    path = _write(tmp_path)
    cols = ["a", "c", "d", "dt"]
    n1, a1, d1, v1 = native.scan_file(path, SCHEMA, cols, threads=1)
    n4, a4, d4, v4 = native.scan_file(path, SCHEMA, cols, threads=4)
    assert n1 == n4 == 5000
    for k in a1:
        np.testing.assert_array_equal(a1[k], a4[k], err_msg=k)
    np.testing.assert_array_equal(d1["c"], d4["c"])
    assert set(v1) == set(v4) == {"a"}
    np.testing.assert_array_equal(v1["a"], v4["a"])
    # decoded strings identical row-wise
    assert list(d1["c"][a1["c"]]) == list(d4["c"][a4["c"]])


def test_mt_composes_with_ranges(tmp_path):
    path = _write(tmp_path)
    size = os.path.getsize(path)
    nA, aA, _, _ = native.scan_file(path, SCHEMA, ["a"], offset=0,
                                    max_bytes=size // 2, threads=3)
    nB, aB, _, _ = native.scan_file(path, SCHEMA, ["a"],
                                    offset=size // 2, threads=3)
    assert nA + nB == 5000
    merged = np.concatenate([aA["a"], aB["a"]])
    # NULL rows parse as 0 in the physical array
    exp = np.array([0 if i % 17 == 0 else i for i in range(5000)])
    np.testing.assert_array_equal(merged, exp)


def test_mt_through_engine_query(tmp_path, monkeypatch):
    """Whole pipeline on a forced-multithreaded scan matches the oracle."""
    monkeypatch.setenv("BALLISTA_SCAN_THREADS", "4")
    path = _write(tmp_path)
    from ballista_tpu.client import BallistaContext
    from ballista_tpu.io import TblSource

    ctx = BallistaContext.standalone()
    ctx.register_source("t", TblSource(path, SCHEMA))
    out = ctx.sql(
        "SELECT c, count(*) AS n, count(a) AS na FROM t GROUP BY c"
    ).collect()
    assert int(out["n"].sum()) == 5000
    # every 17th row has NULL a
    assert int(out["na"].sum()) == 5000 - len(range(0, 5000, 17))


def test_native_binary_is_stale_when_missing_or_older_than_sources(
        tmp_path, monkeypatch):
    """The binaries are git-ignored yet lie on disk: one that is missing
    OR older than its source / the Makefile (a copied tree, changed
    flags) must be rebuilt, not loaded."""
    import os

    lib = tmp_path / "libtblscan.so"
    monkeypatch.setattr(native, "_LIB_PATH", str(lib))
    for name in ("tblscan.cpp", "shuffle_server.cpp", "Makefile"):
        (tmp_path / name).write_text("x")
        os.utime(tmp_path / name, (1000, 1000))
    assert native.stale(str(lib))  # missing
    lib.write_text("bin")
    os.utime(lib, (2000, 2000))
    assert not native.stale(str(lib))
    os.utime(tmp_path / "tblscan.cpp", (3000, 3000))
    assert native.stale(str(lib))  # older than its source
    os.utime(tmp_path / "tblscan.cpp", (1000, 1000))
    os.utime(tmp_path / "Makefile", (3000, 3000))
    assert native.stale(str(lib))  # older than the build flags
    server = tmp_path / "shuffle_server"
    server.write_text("bin")
    os.utime(server, (3500, 3500))
    assert not native.stale(str(server))
    os.utime(tmp_path / "shuffle_server.cpp", (4000, 4000))
    assert native.stale(str(server))
