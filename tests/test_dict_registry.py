"""Dictionary registry (ISSUE 11): device-resident string encodings.

Pins the tentpole contracts:
- producers intern per (table, column) entries -> partitions/re-scans
  share ONE Dictionary instance and unify degenerates to identity;
- version chains remap through pure integer composition; cross-entry
  pairs build once (cached) and match the legacy searchsorted result;
- Arrow IPC stamps resolve to the SAME in-process instance on read;
- q1/q5/q16 results are byte-identical registry ON vs OFF;
- warm q1 pays < 5% for the plane (drift-cancelling scheme, PR-1);
- the vectorized stable_hashes matches the reference FNV-1a loop;
- dev/check_dict_sites.py keeps host unify paths from regrowing.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from ballista_tpu import columnar_registry as reg
from ballista_tpu.client import BallistaContext
from ballista_tpu.columnar import ColumnBatch, Dictionary
from ballista_tpu.datatypes import Int64, Utf8
from ballista_tpu import schema


@pytest.fixture
def registry_env():
    """Force the registry ON for the test and restore after."""
    old = os.environ.pop("BALLISTA_DICT_REGISTRY", None)
    yield
    if old is not None:
        os.environ["BALLISTA_DICT_REGISTRY"] = old


def _fresh_key(tag: str) -> tuple:
    return ("test", tag, time.monotonic_ns())


# ---------------------------------------------------------------------------
# satellite: vectorized stable_hashes
# ---------------------------------------------------------------------------


def _reference_fnv1a(values) -> np.ndarray:
    """The pre-vectorization per-value loop, verbatim (the regression
    anchor: hashes feed shuffle partitioning, so they may NEVER move)."""
    out = np.empty(len(values), dtype=np.int64)
    for i, v in enumerate(values):
        h = 0xCBF29CE484222325
        for b in str(v).encode("utf-8"):
            h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        out[i] = np.int64(np.uint64(h))
    return out


def test_stable_hashes_match_reference_loop():
    import random
    import string

    random.seed(11)
    pool = string.printable.replace("\x00", "")
    vals = ["", "a", "ASIA", "EUROPE", "x" * 300, "héllo wörld",
            "日本語テスト", "a\x00b", "trailing  ", "  leading"]
    vals += ["".join(random.choices(pool, k=random.randint(0, 90)))
             for _ in range(800)]
    d = Dictionary(vals)
    got = d.stable_hashes()
    np.testing.assert_array_equal(got, _reference_fnv1a(vals))
    # cached: the shuffle-partitioning path calls this per evaluation
    assert d.stable_hashes() is got
    assert Dictionary([]).stable_hashes().shape == (0,)


def test_stable_hashes_trailing_nul_exact():
    # numpy's fixed-width str view drops trailing U+0000; the scalar
    # fallback keeps those rows exact
    vals = ["a", "a\x00", "\x00", "", "b\x00\x00"]
    np.testing.assert_array_equal(
        Dictionary(vals).stable_hashes(), _reference_fnv1a(vals))


def test_values_str_cached_and_positions():
    d = Dictionary(["aa", "bb", "cc"])
    sv = d.values_str()
    assert d.values_str() is sv
    np.testing.assert_array_equal(
        d.positions_of(np.asarray(["bb", "aa", "cc"], dtype=object)),
        [1, 0, 2])
    lo, hi = d.code_range("bb")
    assert (lo, hi) == (1, 2)


# ---------------------------------------------------------------------------
# registry core: intern / version chains / remaps
# ---------------------------------------------------------------------------


def test_intern_shares_one_instance(registry_env):
    key = _fresh_key("share")
    d1 = reg.intern(key, ["b", "a", "c"][0:0] + ["a", "b", "c"])
    d2 = reg.intern(key, ["a", "b", "c"])
    assert d1 is d2
    assert reg.REGISTRY.stamp_of(d1) is not None
    # equal content under a DIFFERENT key still collapses by epoch
    d3 = reg.REGISTRY.adopt(None, ["a", "b", "c"])
    assert d3 is d1


def test_intern_version_chain_and_integer_remap(registry_env):
    key = _fresh_key("chain")
    v0 = reg.intern(key, ["b", "d", "f"])
    v1 = reg.intern(key, ["a", "b", "z"])  # superset union appended
    assert v1 is not v0
    assert list(map(str, v1.values)) == ["a", "b", "d", "f", "z"]
    assert v0._reg_version == 0 and v1._reg_version == 1
    # subset of the current version: no new version minted
    assert reg.intern(key, ["a", "z"]) is v1
    # v0 -> v1: pure integer composition, no misses
    r = reg.remap_between(v0, v1)
    np.testing.assert_array_equal(r, [1, 2, 3])
    # v1 -> v0: inverse, absent values -> -1
    r_inv = reg.remap_between(v1, v0)
    np.testing.assert_array_equal(r_inv, [-1, 0, 1, 2, -1])
    # identical coding -> no remap at all
    assert reg.remap_between(v1, v1) is None


def test_remap_between_cross_entry_matches_legacy(registry_env):
    b = reg.intern(_fresh_key("build"), ["ape", "bee", "cat"])
    p = reg.intern(_fresh_key("probe"), ["bee", "cow", "cat"])
    r = reg.remap_between(p, b)
    np.testing.assert_array_equal(r, [1, -1, 2])
    # cached: second call returns the same table
    assert reg.remap_between(p, b) is r
    # legacy (registry off) computes the same mapping
    os.environ["BALLISTA_DICT_REGISTRY"] = "off"
    try:
        np.testing.assert_array_equal(reg.remap_between(p, b), [1, -1, 2])
    finally:
        os.environ.pop("BALLISTA_DICT_REGISTRY")


def test_nul_tail_values_stay_legacy(registry_env):
    # value sets numpy's str representation cannot carry are refused
    d = reg.intern(_fresh_key("nul"), ["a", "a\x00"])
    assert reg.REGISTRY.stamp_of(d) is None
    assert [str(v) for v in d.values] == ["a", "a\x00"]
    # and unify with such a member routes through the object-array
    # union, preserving the value (review fix: the str-view fast path
    # would silently strip the trailing NUL)
    other = reg.intern(_fresh_key("nul-other"), ["a", "b"])
    target, _remaps = reg.unify([d, other])
    vals = [str(v) for v in target.values]
    assert "a\x00" in vals and "b" in vals, vals


# ---------------------------------------------------------------------------
# tentpole: unify is a no-op for shared dictionaries, integer-only
# across versions of one entry
# ---------------------------------------------------------------------------


def _batch(d: Dictionary, codes, extra=0):
    s = schema(("k", Utf8), ("v", Int64))
    return ColumnBatch.from_numpy(
        s,
        {"k": np.asarray(codes, np.int32),
         "v": np.arange(len(codes)) + extra},
        {"k": d}, capacity=8)


def test_concat_unify_noop_for_shared_registry_dict(registry_env):
    from ballista_tpu.physical.base import concat_batches

    d = reg.intern(_fresh_key("noop"), ["x", "y", "z"])
    b1, b2 = _batch(d, [0, 1]), _batch(d, [2, 0], extra=10)
    out = concat_batches(b1.schema, [b1, b2])
    assert out.column("k").dictionary is d  # no union dictionary built
    got = out.to_pydict()
    assert [str(v) for v in got["k"]] == ["x", "y", "z", "x"]


def test_concat_unify_versions_never_touches_legacy_union(registry_env,
                                                          monkeypatch):
    from ballista_tpu.physical.base import concat_batches

    key = _fresh_key("vers")
    v0 = reg.intern(key, ["x", "y"])
    v1 = reg.intern(key, ["w", "x", "y"])

    def boom(*a, **k):  # the object-array union path must not run
        raise AssertionError("legacy union invoked on the registry path")

    monkeypatch.setattr(reg.DictionaryRegistry, "_legacy_union", boom)
    b1, b2 = _batch(v0, [0, 1]), _batch(v1, [0, 2], extra=10)
    out = concat_batches(b1.schema, [b1, b2])
    assert out.column("k").dictionary is v1
    got = out.to_pydict()
    assert [str(v) for v in got["k"]] == ["x", "y", "w", "y"]


def test_unify_parts_adopts_and_collapses(registry_env):
    # shuffle-read shape: raw value arrays from two producers of one
    # table -> one adopted instance, codes pass through unremapped
    vals = np.asarray(["a", "b", "c"], dtype=object)
    target, codes = reg.unify_parts([
        (np.asarray([0, 2], np.int32), vals),
        (np.asarray([1], np.int32), vals.copy()),
    ])
    assert isinstance(target, Dictionary)
    # equal content collapsed to ONE adopted instance, codes untouched
    assert reg.REGISTRY.adopt(None, vals) is target
    np.testing.assert_array_equal(codes[0], [0, 2])
    np.testing.assert_array_equal(codes[1], [1])
    # differing producers still remap onto a shared union
    target2, codes2 = reg.unify_parts([
        (np.asarray([0], np.int32), np.asarray(["a", "c"], dtype=object)),
        (np.asarray([1], np.int32), np.asarray(["b", "c"], dtype=object)),
    ])
    assert [str(v) for v in target2.values] == ["a", "b", "c"]
    np.testing.assert_array_equal(codes2[0], [0])
    np.testing.assert_array_equal(codes2[1], [2])


def test_ipc_roundtrip_resolves_to_interned_instance(registry_env,
                                                     tmp_path):
    from ballista_tpu.io import ipc

    d = reg.intern(_fresh_key("ipc"), ["pp", "qq", "rr"])
    b = _batch(d, [0, 2, 1])
    path = str(tmp_path / "part.arrow")
    ipc.write_partition(path, [b])
    names, arrays, nulls, dicts, kinds = ipc.read_partition_arrays(path)
    assert dicts["k"] is d  # stamp resolved, values never re-hydrated
    fp = ipc.read_partition_pieces(path)
    assert fp.dicts["k"] is d
    batches, _ = ipc.batches_from_pieces(b.schema, [fp])
    assert batches[0].column("k").dictionary is d


# ---------------------------------------------------------------------------
# determinism + overhead gates
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tpch_dir(tmp_path_factory):
    from benchmarks.tpch import datagen

    d = str(tmp_path_factory.mktemp("tpch_reg"))
    datagen.generate(d, scale=0.005, num_parts=2)
    return d


def _collect_queries(data_dir, queries):
    from benchmarks.tpch.schema_def import register_tpch

    ctx = BallistaContext.standalone()
    register_tpch(ctx, data_dir, "tbl")
    qdir = os.path.join(REPO, "benchmarks", "tpch", "queries")
    out = {}
    for q in queries:
        df = ctx.sql(open(os.path.join(qdir, f"{q}.sql")).read())
        out[q] = df.collect()
    return out


def test_determinism_registry_on_vs_off(tpch_dir):
    queries = ("q1", "q5", "q16")
    old = os.environ.pop("BALLISTA_DICT_REGISTRY", None)
    try:
        on = _collect_queries(tpch_dir, queries)
        os.environ["BALLISTA_DICT_REGISTRY"] = "off"
        off = _collect_queries(tpch_dir, queries)
    finally:
        if old is not None:
            os.environ["BALLISTA_DICT_REGISTRY"] = old
        else:
            os.environ.pop("BALLISTA_DICT_REGISTRY", None)
    for q in queries:
        assert list(on[q].columns) == list(off[q].columns)
        for col in on[q].columns:
            a = on[q][col].to_numpy()
            b = off[q][col].to_numpy()
            if a.dtype.kind == "O" or b.dtype.kind == "O":
                assert [str(x) for x in a] == [str(x) for x in b], \
                    f"{q}.{col} differs registry on vs off"
            else:
                np.testing.assert_array_equal(
                    a, b, err_msg=f"{q}.{col} differs registry on vs off")


def test_registry_overhead_q1_under_5pct(tpch_dir):
    """Warm q1 with the registry ON stays within 5% of OFF — the
    drift-cancelling scheme (alternating interleaved samples, medians,
    retries) from the PR-1 gates. The warm path performs no unify at
    all; this pins that the plane stays off it."""
    from benchmarks.tpch.schema_def import register_tpch

    ctx = BallistaContext.standalone()
    register_tpch(ctx, tpch_dir, "tbl")
    qdir = os.path.join(REPO, "benchmarks", "tpch", "queries")
    df = ctx.sql(open(os.path.join(qdir, "q1.sql")).read())
    df.collect()  # warm: jit compile + table caches

    def set_enabled(on: bool):
        if on:
            os.environ.pop("BALLISTA_DICT_REGISTRY", None)
        else:
            os.environ["BALLISTA_DICT_REGISTRY"] = "off"

    def sample(on: bool):
        set_enabled(on)
        t0 = time.perf_counter()
        for _ in range(3):
            df.collect()
        return time.perf_counter() - t0

    try:
        sample(True)
        sample(False)

        def measure():
            offs, ons = [], []
            for i in range(9):
                if i % 2 == 0:
                    offs.append(sample(False))
                    ons.append(sample(True))
                else:
                    ons.append(sample(True))
                    offs.append(sample(False))
            return sorted(offs)[4], sorted(ons)[4]

        for _attempt in range(3):
            t_off, t_on = measure()
            if t_on <= t_off * 1.05 + 2e-3:
                break
        else:
            overhead = (t_on - t_off) / t_off
            raise AssertionError(
                f"dictionary-registry overhead {overhead:.1%} "
                f"(on={t_on:.4f}s off={t_off:.4f}s)")
    finally:
        set_enabled(True)


# ---------------------------------------------------------------------------
# tooling
# ---------------------------------------------------------------------------


def test_dict_sites_lint_clean():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "dev", "check_dict_sites.py")],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_dict_sites_lint_detects(tmp_path):
    # the lint actually fires on a host unify site outside the registry
    # (staged tree ships the shim + the analysis engine it runs on; the
    # engine loads standalone, so no ballista_tpu/__init__ is needed)
    import shutil

    stage = tmp_path / "repo"
    (stage / "dev").mkdir(parents=True)
    pkg = stage / "ballista_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import numpy as np\n"
        "def unify(dicts):\n"
        "    return np.unique(np.concatenate(dicts))\n")
    for f in ("check_dict_sites.py", "analyze.py"):
        shutil.copy(os.path.join(REPO, "dev", f), stage / "dev" / f)
    shutil.copytree(os.path.join(REPO, "ballista_tpu", "analysis"),
                    pkg / "analysis",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(stage / "dev" / "check_dict_sites.py")],
        capture_output=True, text=True)
    assert r.returncode == 1 and "rogue.py" in r.stderr
