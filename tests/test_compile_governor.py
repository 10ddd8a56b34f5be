"""Compile governor (PR 3): shape-bucket ladder, unified jit cache,
compile observability.

Layers, bottom-up: ladder math + knobs; governor entry
sharing/attribution/eviction units; the partition-size-jitter pin (same
plan over N distinct row counts compiles at most once per ladder rung,
not once per count); the adaptive-re-plan regression (a re-built plan
performs ZERO new compiles for unchanged signatures — the per-instance
``self._jit_*`` dicts this PR deleted used to throw every trace away);
a masked-correctness sweep (bucket-padded results row-identical to
unpadded across agg/sort/join/limit); the one-way-to-compile pins; and the
``dev/check_jit_sites.py`` lint so the scattered-cache problem can't
regrow. Also hosts the BALLISTA_XLA_CACHE_MIN_COMPILE_SECS default pin.
"""

import os
import sys

import numpy as np
import pytest

from ballista_tpu import Int64, Utf8, col, lit, schema
from ballista_tpu.client import BallistaContext
from ballista_tpu.compile import (
    bucket_capacity,
    bucket_ladder,
    compile_stats,
    governed,
    governor,
    reconfigure,
)


@pytest.fixture
def bucket_env(monkeypatch):
    """Set BALLISTA_SHAPE_BUCKETS* env for a test and re-read it,
    restoring the default config afterwards."""

    def set_env(**kv):
        for k, v in kv.items():
            name = "BALLISTA_SHAPE_BUCKETS" + (f"_{k.upper()}" if k else "")
            monkeypatch.setenv(name, str(v))
        reconfigure()

    yield set_env
    monkeypatch.undo()
    reconfigure()


# ---------------------------------------------------------------------------
# ladder math + knobs
# ---------------------------------------------------------------------------


def test_bucket_ladder_defaults():
    assert bucket_capacity(0) == 1024  # floor
    assert bucket_capacity(1) == 1024
    assert bucket_capacity(1024) == 1024
    assert bucket_capacity(1025) == 2048
    assert bucket_capacity(6_001_215) == 1 << 23
    assert bucket_ladder(100_000) == [1024, 2048, 4096, 8192, 16384,
                                      32768, 65536, 131072]


def test_bucket_knobs(bucket_env):
    bucket_env(floor=4096, growth=4)
    assert bucket_capacity(10) == 4096
    assert bucket_capacity(5000) == 16384
    assert bucket_ladder(100_000) == [4096, 16384, 65536, 262144]
    # non-power-of-two knobs snap up
    bucket_env(floor=1000, growth=3)
    assert bucket_capacity(10) == 1024
    assert bucket_capacity(2000) == 4096  # growth 3 -> 4


def test_buckets_off_is_exact_pow2(bucket_env):
    bucket_env(**{"": "off"})
    assert bucket_capacity(10) == 16
    assert bucket_capacity(600) == 1024
    assert bucket_capacity(3) == 8  # minimum still holds


# ---------------------------------------------------------------------------
# governor units
# ---------------------------------------------------------------------------


def test_governed_entry_shared_and_counted():
    import jax.numpy as jnp

    built = []

    def build():
        built.append(1)
        return lambda x: x + 1

    key = ("test.unit", "shared")
    f1 = governed(key, build)
    f2 = governed(key, build)
    assert f1 is f2
    assert built == [1]  # second lookup did not rebuild
    out = f1(jnp.asarray(1))
    assert int(out) == 2
    assert f1.calls >= 1


def test_governed_programs_are_named_by_key_family():
    """Two governed functions of different families lower to XLA modules
    named for their families, not for the inner function (`run`) their
    builds return; a shared kernel function is wrapped, not renamed."""
    import jax.numpy as jnp

    from ballista_tpu.compile.governor import program_name

    def shared_kernel(x):
        return x * 2

    def build_run():
        def run(x):
            return x + 1
        return run

    probe = governed(("join.probe_dense", ("sig", 1 << 20), "inner"),
                     build_run)
    # not a rung: the governor is process-wide, and a real compaction to
    # 4,096 rows later in this worker must not be handed this kernel
    compact = governed(("batch.compact", "shared-kernel"),
                       lambda: shared_kernel)
    x = jnp.arange(8)
    assert "@jit_join_probe_dense" in probe.fn.lower(x).as_text()
    assert "@jit_batch_compact" in compact.fn.lower(x).as_text()
    assert "jit_run" not in probe.fn.lower(x).as_text()
    assert shared_kernel.__name__ == "shared_kernel"
    assert int(probe(x)[1]) == 2 and int(compact(x)[1]) == 2
    # no shapes, no literals, short
    assert program_name(("pipeline.fused.don", ("Filter: x > 3",))) == \
        "pipeline_fused_don"
    assert len(program_name(("a" * 80,))) < 40
    assert program_name(()) == "governed"


def test_governed_namespace_eviction():
    gov = governor()
    gov.clear("test.evict")
    for i in range(5):
        governed(("test.evict", i), lambda: (lambda x: x), cap=3)
    assert gov.namespace_sizes().get("test.evict") == 3
    gov.clear("test.evict")


def test_governed_build_may_request_governed_entries():
    """Deadlock regression: a build() that itself asks the governor for
    another entry (mesh SPMD programs wrap an aggregate's grouped
    kernel) must not self-deadlock — entries build outside the lock."""
    import jax.numpy as jnp

    gov = governor()
    gov.clear("test.nested")

    def inner_build():
        return lambda x: x * 2

    def outer_build():
        inner = governed(("test.nested", "inner"), inner_build)
        return lambda x: inner(x) + 1

    out = governed(("test.nested", "outer"), outer_build)(jnp.asarray(3))
    assert int(out) == 7
    gov.clear("test.nested")


def test_governed_compile_attribution_to_metrics():
    import jax.numpy as jnp

    from ballista_tpu.observability.metrics import MetricsSet

    m = MetricsSet()
    # a fresh closure constant guarantees a fresh XLA program
    fn = governed(("test.unit", "attrib"),
                  lambda: (lambda x: x * 3 + 17), metrics=m)
    fn(jnp.arange(1024))
    vals = m.values()
    assert vals.get("compile_count", 0) >= 1
    assert vals.get("elapsed_compile", 0.0) > 0.0
    st = compile_stats()
    assert st["backend_compiles"] >= 1
    assert st["entries"] >= 1


# ---------------------------------------------------------------------------
# partition-size jitter: compiles bounded by the ladder, not the counts
# ---------------------------------------------------------------------------


def _jitter_ctx(n: int) -> BallistaContext:
    s = schema(("k", Int64), ("v", Int64))
    ctx = BallistaContext.standalone()
    ctx.register_memtable("t", s, {
        "k": (np.arange(n) % 7).astype(np.int64),
        "v": np.arange(n, dtype=np.int64),
    })
    return ctx


_JITTER_SQL = ("SELECT k, SUM(v) AS sv, COUNT(*) AS c FROM t "
               "GROUP BY k ORDER BY k")


def _expected(n: int):
    k = (np.arange(n) % 7).astype(np.int64)
    v = np.arange(n, dtype=np.int64)
    return {int(g): (int(v[k == g].sum()), int((k == g).sum()))
            for g in range(7)}


def _compile_requests() -> int:
    """backend compiles + persistent-disk-cache hits: every compile
    REQUEST, whether or not the disk cache absorbed it. A recompile
    served from disk still means the in-memory trace was not reused."""
    st = compile_stats()
    return int(st["backend_compiles"]) + int(st["persistent_cache_hits"])


def test_partition_size_jitter_bounded_by_ladder():
    """N distinct row counts -> compiles happen only when a NEW ladder
    rung is first seen; re-running at other counts on a known rung
    performs zero compile requests (fresh context + fresh operator
    instances every time). The counts are chosen to round to DIFFERENT
    power-of-two capacities (128/512/1024), so per-exact-shape caching —
    the pre-governor behavior — fails this test."""
    counts_rung1 = [100, 300, 600, 1000]   # all bucket to the 1024 floor
    counts_rung2 = [1500, 1800, 2048]      # all bucket to 2048
    assert {bucket_capacity(n) for n in counts_rung1} == {1024}
    assert {bucket_capacity(n) for n in counts_rung2} == {2048}

    def run(n):
        ctx = _jitter_ctx(n)
        out = ctx.sql(_JITTER_SQL).collect()
        exp = _expected(n)
        got = {int(r.k): (int(r.sv), int(r.c)) for r in out.itertuples()}
        assert got == exp

    run(counts_rung1[0])  # first sight of rung 1024: compiles allowed
    base = _compile_requests()
    for n in counts_rung1[1:]:
        run(n)
    assert _compile_requests() == base, \
        "distinct row counts on one ladder rung must not recompile"
    run(counts_rung2[0])  # first sight of rung 2048: compiles allowed
    base2 = _compile_requests()
    for n in counts_rung2[1:]:
        run(n)
    assert _compile_requests() == base2


# ---------------------------------------------------------------------------
# re-plan regression: new operator instances reuse every governed trace
# ---------------------------------------------------------------------------


def _replan_ctx() -> BallistaContext:
    ctx = BallistaContext.standalone()
    n = 1200
    rng = np.random.RandomState(7)
    ctx.register_memtable("orders_r", schema(
        ("okey", Int64), ("ckey", Int64), ("amount", Int64)), {
        "okey": np.arange(n, dtype=np.int64),
        "ckey": rng.randint(0, 40, n).astype(np.int64),
        "amount": rng.randint(0, 1000, n).astype(np.int64),
    })
    ctx.register_memtable("cust_r", schema(
        ("ckey", Int64), ("name", Utf8)), {
        "ckey": np.arange(40, dtype=np.int64),
        "name": [f"c{i % 5}" for i in range(40)],
    })
    return ctx


_REPLAN_SQL = (
    "SELECT name, COUNT(*) AS n, SUM(amount) AS amt "
    "FROM orders_r JOIN cust_r ON orders_r.ckey = cust_r.ckey "
    "WHERE amount > 100 GROUP BY name ORDER BY name"
)


def test_replan_performs_zero_new_compiles():
    """The satellite regression: re-planning (fresh physical operator
    instances over the same logical plan — what adaptive execution does
    on every stage completion) must hit the governor for every kernel.
    The old per-instance ``_jit_probe`` / ``_jit_cache`` dicts leaked
    exactly here."""
    ctx = _replan_ctx()
    first = ctx.sql(_REPLAN_SQL).collect()
    # fresh DataFrame -> plan_logical runs again -> ALL-NEW operator
    # instances (same signatures)
    ctx._plan_cache.clear()
    before = _compile_requests()
    second = ctx.sql(_REPLAN_SQL).collect()
    after = _compile_requests()
    assert after == before, (
        f"re-planned query issued {after - before} new compile "
        "requests; unchanged signatures must reuse governed entries")
    assert first.equals(second)


def test_governed_entries_do_not_pin_plans():
    """Memory regression: governed closures capture config-only trace
    twins, never the live operators — else the process-wide cache would
    pin plan subtrees (cached scan batches, join build-side device
    buffers) until LRU eviction."""
    import gc
    import weakref

    ctx = _replan_ctx()
    df = ctx.sql(_REPLAN_SQL)
    df.collect()
    refs = []

    def walk(n):
        refs.append(weakref.ref(n))
        for c in n.children():
            walk(c)

    walk(df._phys)
    assert refs
    del df, ctx
    gc.collect()
    alive = [r() for r in refs if r() is not None]
    assert not alive, (
        f"{len(alive)} operator(s) still pinned after the plan died: "
        f"{[type(a).__name__ for a in alive]}")


# ---------------------------------------------------------------------------
# masked correctness: bucket padding is row-identical to exact shapes
# ---------------------------------------------------------------------------


def _sweep_ctx() -> BallistaContext:
    ctx = BallistaContext.standalone()
    n = 1337  # deliberately off-rung
    rng = np.random.RandomState(3)
    amount = rng.randint(-50, 1000, n).astype(np.int64)
    valid = rng.rand(n) > 0.1  # ~10% NULLs through the agg paths
    ctx.register_memtable("fact_s", schema(
        ("id", Int64), ("grp", Utf8), ("dkey", Int64),
        ("amount", Int64)), {
        "id": np.arange(n, dtype=np.int64),
        "grp": [f"g{i % 11}" for i in range(n)],
        "dkey": rng.randint(0, 23, n).astype(np.int64),
        "amount": amount,
    })
    # dim table sized 23 (tiny, well under the floor)
    ctx.register_memtable("dim_s", schema(
        ("dkey", Int64), ("label", Utf8)), {
        "dkey": np.arange(23, dtype=np.int64),
        "label": [f"l{i % 4}" for i in range(23)],
    })
    return ctx


_SWEEP_SQLS = [
    # aggregate (grouped, utf8 + int keys)
    "SELECT grp, COUNT(*) AS n, SUM(amount) AS s, MIN(amount) AS mn, "
    "MAX(amount) AS mx FROM fact_s GROUP BY grp ORDER BY grp",
    # scalar aggregate
    "SELECT COUNT(*) AS n, SUM(amount) AS s FROM fact_s",
    # join + aggregate
    "SELECT label, COUNT(*) AS n, SUM(amount) AS s FROM fact_s "
    "JOIN dim_s ON fact_s.dkey = dim_s.dkey GROUP BY label ORDER BY label",
    # filter + sort + limit
    "SELECT id, amount FROM fact_s WHERE amount > 500 "
    "ORDER BY amount DESC, id LIMIT 17",
    # semi-ish subquery shape
    "SELECT COUNT(*) AS n FROM fact_s WHERE dkey IN "
    "(SELECT dkey FROM dim_s WHERE label = 'l1')",
]


def test_masked_correctness_bucket_on_vs_off(bucket_env):
    got_on = []
    for q in _SWEEP_SQLS:  # default: buckets on
        got_on.append(_sweep_ctx().sql(q).collect())
    bucket_env(**{"": "off"})
    for q, on in zip(_SWEEP_SQLS, got_on):
        off = _sweep_ctx().sql(q).collect()
        assert on.equals(off), f"bucketed result differs for: {q}"


def test_bucketed_batch_padding_is_dead():
    """Entry-boundary pin: from_numpy pads to the ladder rung and the
    padding rows are unselected, invisible to collect."""
    from ballista_tpu.columnar import ColumnBatch

    s = schema(("a", Int64))
    b = ColumnBatch.from_numpy(s, {"a": np.arange(37, dtype=np.int64)})
    assert b.capacity == bucket_capacity(37)
    assert int(b.num_rows) == 37
    out = b.to_pydict()
    assert list(out["a"]) == list(range(37))


# ---------------------------------------------------------------------------
# observability surfaces
# ---------------------------------------------------------------------------


def test_compile_metrics_reach_explain_analyze():
    ctx = BallistaContext.standalone()
    n = 900
    # a schema unique to this test guarantees fresh signatures -> at
    # least one real compile lands inside the ANALYZE window
    ctx.register_memtable("ea_compile_t", schema(
        ("ea_k", Int64), ("ea_v", Int64)), {
        "ea_k": (np.arange(n) % 5).astype(np.int64),
        "ea_v": np.arange(n, dtype=np.int64),
    })
    out = ctx.sql(
        "EXPLAIN ANALYZE SELECT ea_k, SUM(ea_v) AS s FROM ea_compile_t "
        "WHERE ea_v > 13 GROUP BY ea_k ORDER BY ea_k").collect()
    text = dict(zip(out["plan_type"], out["plan"]))["plan_with_metrics"]
    assert "compile_count=" in text
    assert "elapsed_compile=" in text


def test_trace_span_emitted_for_compiles(tmp_path, monkeypatch):
    import json

    from ballista_tpu.observability import tracing

    trace_file = tmp_path / "trace.jsonl"
    monkeypatch.setenv("BALLISTA_TRACE", "1")
    monkeypatch.setenv("BALLISTA_TRACE_FILE", str(trace_file))
    tracing.reconfigure()
    try:
        import jax.numpy as jnp

        fn = governed(("test.unit", "traced"),
                      lambda: (lambda x: x * 5 - 2))
        fn(jnp.arange(512))
    finally:
        monkeypatch.undo()
        tracing.reconfigure()
    lines = [json.loads(l) for l in trace_file.read_text().splitlines()]
    spans = [l for l in lines if l["name"] == "compile.jit"]
    assert spans and spans[0]["compiles"] >= 1
    assert "test.unit" in spans[0]["key"]


def test_persistent_cache_min_compile_secs_defaults_to_zero():
    import jax

    # ballista_tpu/__init__.py only configures the cache when the dir is
    # writable; when it did, the knob default must be 0 (cache EVERY
    # kernel — the 0.1s floor silently excluded small ones)
    if jax.config.jax_compilation_cache_dir:
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert os.environ.get("BALLISTA_XLA_CACHE_MIN_COMPILE_SECS") is None


@pytest.mark.parametrize("from_env", [True, False],
                         ids=["env-dir", "checkout-dir"])
def test_compile_cache_placed_from_outside(tmp_path, from_env):
    """Where JAX_COMPILATION_CACHE_DIR is set the package sets no
    directory in code; where it is not, the cache is the ONE fixed path
    inside the checkout (a moved directory never hits: the path is part
    of the cache key)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, ballista_tpu\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "print(ballista_tpu.XLA_CACHE_DIR)"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    used, fixed = out.stdout.split()
    repo = os.path.realpath(os.path.join(os.path.dirname(__file__), ".."))
    assert os.path.realpath(fixed) == os.path.join(repo, ".xla_cache")
    assert used == (str(tmp_path / "cc") if from_env else fixed)
    # the directory is never created by the package when the env names it
    assert not (tmp_path / "cc").exists()


# ---------------------------------------------------------------------------
# one way to obtain a program: jax.jit under the governor
# ---------------------------------------------------------------------------


def test_governed_takes_no_aot_argument_and_counts_no_aot_or_prewarm():
    import inspect

    from ballista_tpu.execution import plan_logical

    assert "aot" not in inspect.signature(governed).parameters
    assert "aot" not in inspect.signature(governor().get).parameters
    with pytest.raises(TypeError):
        governed(("test.unit", "no-aot"), lambda: (lambda x: x), aot=True)
    with pytest.raises(TypeError):
        plan_logical(_replan_ctx().sql(_REPLAN_SQL).plan).governed_jit(
            ("test.unit",), lambda: (lambda x: x), aot=True)
    assert not [k for k in compile_stats()
                if k.startswith(("aot_", "prewarm_"))]
    assert all("aot_loads" not in row for row in governor().entry_rows())


def test_equal_calls_compile_once_and_programs_keep_their_names(tmp_path):
    """A governed call made twice with equal arguments compiles once,
    and the programs the benchmark's cells show keep the names they had
    (a renamed program is a new cache key: it compiles again)."""
    import jax.numpy as jnp

    from ballista_tpu.compile.governor import program_name

    fn = governed(("test.unit.once", 1024), lambda: (lambda x: x * 3 + 1))
    x = jnp.arange(1024, dtype=jnp.int64)
    out = fn(x)
    # the entry's own count (a disk-cache retrieval is a trip through
    # the backend's compile call too, and counts in both)
    assert fn.compiles == 1 and fn.pcache_hits <= 1
    seen = (fn.compiles, fn.pcache_hits)
    again = fn(jnp.arange(1024, dtype=jnp.int64))
    assert (fn.compiles, fn.pcache_hits) == seen and fn.calls == 2
    assert int(out[2]) == int(again[2]) == 7

    # the keys the operators really use, taken from real queries: a
    # filter's compaction, a donating dense aggregate, a unique-key join
    n = 3000
    (tmp_path / "f.tbl").write_text(
        "".join(f"{i}|{i % 7}|{'abc'[i % 3]}|{i * 2}|\n" for i in range(n)))
    (tmp_path / "d.tbl").write_text(
        "".join(f"{i}|{i * 10}|\n" for i in range(7)))
    ctx = BallistaContext.standalone()
    ctx.register_tbl("pn_f", str(tmp_path / "f.tbl"), schema(
        ("fk", Int64), ("fd", Int64), ("ff", Utf8), ("fv", Int64)))
    ctx.register_tbl("pn_d", str(tmp_path / "d.tbl"),
                     schema(("dk", Int64), ("dw", Int64)),
                     primary_key=["dk"])
    flags = ctx.sql("SELECT ff, SUM(fv) AS s FROM pn_f WHERE fk < 10 "
                    "GROUP BY ff ORDER BY ff").collect()
    assert list(flags["s"]) == [36, 24, 30]
    joined = ctx.sql("SELECT dw, fv FROM pn_f JOIN pn_d ON fd = dk "
                     "WHERE fk < 10 ORDER BY fv").collect()
    assert list(joined["dw"]) == [(i % 7) * 10 for i in range(10)]
    names = {program_name((ns,))
             for ns, size in governor().namespace_sizes().items() if size}
    assert {"batch_compact", "agg_grouped_don", "join_unique"} <= names, \
        sorted(names)


# ---------------------------------------------------------------------------
# lint: no raw jax.jit outside ballista_tpu/compile/
# ---------------------------------------------------------------------------


def test_no_raw_jit_sites_outside_compile():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "dev"))
    try:
        import check_jit_sites
    finally:
        sys.path.pop(0)
    hits = check_jit_sites.scan()
    assert hits == [], "\n".join(f"{r}:{i}: {l}" for r, i, l in hits)
