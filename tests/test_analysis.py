"""Unified static-analysis engine tests (ballista_tpu/analysis/ +
dev/analyze.py) and regression pins for the real bugs its first run
surfaced.

Layout:
- fixture snippets per rule: one that trips, one clean, one suppressed,
  one baselined (the ISSUE 13 acceptance matrix);
- the tier-1 wiring: ONE ``dev/analyze.py --baseline
  dev/analysis_baseline.json`` subprocess over the whole package must
  exit 0 inside the 10s runtime budget (this replaces N per-lint
  shells; the old ``dev/check_*.py`` entry points stay as shims and
  keep their own tests);
- regression tests for the fixes: cancel checks in the parquet/text
  scan chunk loops, the dataplane fetch loops and the IPC decode/
  assembly paths, plus ``device.block`` spans on the shuffle-write and
  result-materialization syncs.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from ballista_tpu import Int64, Utf8, schema
from ballista_tpu import analysis
from ballista_tpu.columnar import ColumnBatch
from ballista_tpu.errors import QueryCancelled
from ballista_tpu.io import ipc
from ballista_tpu.lifecycle import CancelToken, bind_token
from ballista_tpu.observability import tracing

REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
ANALYZE = os.path.join(REPO, "dev", "analyze.py")


def _pkg(tmp_path, files):
    root = tmp_path / "fixroot"
    for rel, text in files.items():
        p = root / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return analysis.Package.load(str(root), package_rel="fixpkg")


def _run(pkg, rule, baseline=None):
    return analysis.analyze(pkg, [rule], baseline)


# ---------------------------------------------------------------------------
# engine mechanics: suppression, baseline, stale entries
# ---------------------------------------------------------------------------


def test_suppression_and_baseline_mechanics(tmp_path):
    trip = """
        import jax

        def f(x):
            return jax.device_get(x)
    """
    suppressed = """
        import jax

        def f(x):
            return jax.device_get(x)  # ballista: ignore[sync-span] host scalar
    """
    pkg = _pkg(tmp_path, {"fixpkg/trip.py": trip,
                          "fixpkg/ok.py": suppressed})
    rule = analysis.RULE_FACTORIES["sync-span"]()
    res = _run(pkg, rule)
    assert [f.file for f in res.findings] == ["fixpkg/trip.py"]
    assert res.suppressed == 1

    # baselined: the same finding matched by (rule, file, anchor)
    f = res.findings[0]
    bl = analysis.Baseline([{"rule": f.rule, "file": f.file,
                             "anchor": f.anchor, "note": "fixture"}])
    res2 = _run(pkg, rule, bl)
    assert res2.findings == [] and len(res2.baselined) == 1
    assert res2.ok

    # a stale entry (site fixed/moved away) is reported, not fatal
    bl2 = analysis.Baseline([{"rule": f.rule, "file": f.file,
                              "anchor": "gone_anchor()", "note": "old"}])
    res3 = _run(pkg, rule, bl2)
    assert len(res3.stale) == 1 and not res3.ok  # finding unbaselined


def test_comment_only_suppression_covers_next_line(tmp_path):
    src = """
        import jax

        def f(x):
            # ballista: ignore[sync-span] resolved scalars only
            return jax.device_get(x)
    """
    pkg = _pkg(tmp_path, {"fixpkg/m.py": src})
    res = _run(pkg, analysis.RULE_FACTORIES["sync-span"]())
    assert res.findings == [] and res.suppressed == 1


# ---------------------------------------------------------------------------
# cancel-coverage fixtures
# ---------------------------------------------------------------------------


def test_cancel_coverage_trips_and_clean(tmp_path):
    src = """
        from .lc import check_cancel

        def bad(plan, parts):
            out = []
            for batch in plan.execute(parts):
                out.append(decode(batch))
            return out

        def good(plan, parts):
            out = []
            for batch in plan.execute(parts):
                check_cancel()
                out.append(decode(batch))
            return out

        def metadata_only(locs):
            seen = {}
            for part in locs:
                seen[part.stage] = part.rows
            return seen
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src,
                          "fixpkg/lc.py": "def check_cancel():\n    pass\n"})
    from ballista_tpu.analysis.passes.cancel_coverage import (
        CancelCoverageRule,
    )

    rule = CancelCoverageRule(critical_modules={"fixpkg/mod.py"})
    res = _run(pkg, rule)
    assert len(res.findings) == 1
    assert "bad" in res.findings[0].message


def test_cancel_coverage_follows_one_call_level(tmp_path):
    src = """
        from .lc import check_cancel

        def _pump(x):
            check_cancel()
            return x

        def covered(stream):
            for chunk in stream:
                _pump(chunk)

        class Reader:
            def _bail(self):
                check_cancel()

            def covered_method(self, stream):
                for chunk in stream:
                    self._bail()
                    use(chunk)
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src,
                          "fixpkg/lc.py": "def check_cancel():\n    pass\n"})
    from ballista_tpu.analysis.passes.cancel_coverage import (
        CancelCoverageRule,
    )

    rule = CancelCoverageRule(critical_modules={"fixpkg/mod.py"})
    assert _run(pkg, rule).findings == []


def test_cancel_coverage_satisfiers_are_receiver_gated(tmp_path):
    """An unrelated validator.check(b) or future-style .cancelled probe
    must NOT satisfy the rule; token-ish receivers must."""
    src = """
        def bad(batches, validator):
            for b in batches:
                validator.check(b)
                process(b)

        def bad2(batches, fut):
            for b in batches:
                if fut.cancelled():
                    break
                process(b)

        def ok(batches, token):
            for b in batches:
                token.check()
                process(b)

        def ok2(batches, cancel_token):
            for b in batches:
                if cancel_token.cancelled:
                    break
                process(b)
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src})
    from ballista_tpu.analysis.passes.cancel_coverage import (
        CancelCoverageRule,
    )

    rule = CancelCoverageRule(critical_modules={"fixpkg/mod.py"})
    found = {f.message.split(" ")[3] for f in _run(pkg, rule).findings}
    assert found == {"bad", "bad2"}, found


# ---------------------------------------------------------------------------
# sync-span fixtures
# ---------------------------------------------------------------------------


def test_sync_span_matrix(tmp_path):
    src = """
        import jax
        import numpy as np
        from .tr import trace_span

        def bad(col):
            return np.asarray(col.values)

        def bad2(x):
            return jax.device_get(x)

        def spanned(col):
            with trace_span("device.block", site="fix"):
                return np.asarray(col.selection)

        def host_object(d):
            return np.asarray(d.values, dtype=object)

        def host_input(rows):
            return np.asarray([r for r in rows])

        def provenance(b):
            import jax.numpy as jnp
            y = jnp.sum(b)
            return np.asarray(y)
    """
    pkg = _pkg(tmp_path, {
        "fixpkg/mod.py": src,
        "fixpkg/tr.py": ("from contextlib import contextmanager\n"
                         "@contextmanager\n"
                         "def trace_span(name, **kw):\n    yield\n"),
    })
    res = _run(pkg, analysis.RULE_FACTORIES["sync-span"]())
    lines = sorted(f.line for f in res.findings)
    msgs = " | ".join(f.message for f in res.findings)
    assert len(res.findings) == 3, msgs
    assert "np.asarray on a device value" in msgs
    assert "device_get" in msgs
    # spanned / dtype=object / host-list sites are NOT findings
    assert all(f.file == "fixpkg/mod.py" for f in res.findings)
    assert lines == sorted(lines)


def test_h2d_discipline_matrix(tmp_path):
    """Scan-source uploads must sit behind serve_or_fill: direct
    uploads in scan(), or in a module that never routes through the
    residency layer, are findings; produce-callback uploads and
    non-scan modules (shuffle codecs) are not."""
    unrouted = """
        from ..columnar import ColumnBatch

        class RogueSource:
            def scan(self, partition):
                yield from self._parts[partition]

            @classmethod
            def from_data(cls, schema, data):
                return [ColumnBatch.from_numpy(schema, data, {}, 8)]
    """
    routed = """
        import jax.numpy as jnp
        from ..columnar import ColumnBatch
        from ..cache.residency import serve_or_fill

        class GoodSource:
            def scan(self, partition):
                yield from serve_or_fill(
                    self._key(partition),
                    lambda: self._scan_direct(partition))

            def _scan_direct(self, partition):
                yield ColumnBatch.from_numpy(
                    self._schema, self._arrays[partition], {}, 8)

        class FrontRunner:
            def scan(self, partition):
                for arr in self._arrays[partition]:
                    yield jnp.asarray(arr)  # upload BEFORE the layer
    """
    codec = """
        import jax.numpy as jnp

        def decode(vals):
            return jnp.asarray(vals)  # shuffle wire codec: no scan
    """
    pkg = _pkg(tmp_path, {
        "fixpkg/io/unrouted.py": unrouted,
        "fixpkg/io/routed.py": routed,
        "fixpkg/io/codec.py": codec,
    })
    res = _run(pkg, analysis.RULE_FACTORIES["h2d-discipline"]())
    by_file = {}
    for f in res.findings:
        by_file.setdefault(f.file, []).append(f.message)
    assert list(by_file.get("fixpkg/io/unrouted.py", [])), by_file
    assert "never routes through" in by_file["fixpkg/io/unrouted.py"][0]
    assert len(by_file.get("fixpkg/io/routed.py", [])) == 1, by_file
    assert "in front of the residency layer" in \
        by_file["fixpkg/io/routed.py"][0]
    assert "fixpkg/io/codec.py" not in by_file


def test_h2d_discipline_real_tree_clean():
    """The live io/ sources hold the discipline (memory.py's
    registration-time upload is the one triaged baseline entry)."""
    pkg = analysis.Package.load(REPO)
    res = _run(pkg, analysis.RULE_FACTORIES["h2d-discipline"]())
    files = sorted({f.file for f in res.findings})
    assert files == ["ballista_tpu/io/memory.py"], files


# ---------------------------------------------------------------------------
# lock-discipline fixtures
# ---------------------------------------------------------------------------


def test_lock_discipline_matrix(tmp_path):
    src = """
        import threading

        _lock = threading.Lock()
        _cache = {}
        _safe = {}

        def bad_write(k, v):
            _cache[k] = v

        def good_write(k, v):
            with _lock:
                _safe[k] = v

        def _fill_locked(k, v):
            _cache[k] = v

        def dcl(key, locks):
            if key not in _cache:
                with _lock:
                    if key not in _cache:
                        _cache[key] = 1
            return _cache[key]

        def keyed(key, key_locks):
            if key not in _cache:
                with key_locks.get(key):
                    if key not in _cache:
                        with _lock:
                            _cache[key] = 1
            return _cache[key]
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src})
    res = _run(pkg, analysis.RULE_FACTORIES["lock-discipline"]())
    by_msg = {}
    for f in res.findings:
        kind = ("dcl" if "double-checked" in f.message else "write")
        by_msg.setdefault(kind, []).append(f.line)
    # exactly one unguarded write (bad_write; *_locked exempt, dcl's
    # write is under the lock) and one hand-rolled DCL (keyed() uses
    # the KeyedLocks carrier and is exempt)
    assert len(by_msg.get("write", [])) == 1, res.findings
    assert len(by_msg.get("dcl", [])) == 1, res.findings


# ---------------------------------------------------------------------------
# migrated code-shape lints: seeded-violation parity
# ---------------------------------------------------------------------------


def test_jit_and_dict_rules_fire_on_seeded_violations(tmp_path):
    src = """
        import jax
        import numpy as np

        def rogue(xs, dicts):
            f = jax.jit(lambda x: x + 1)
            u = np.unique(np.concatenate(dicts))
            return f(xs), u

        def opted_out(xs, dicts):
            f = jax.jit(lambda x: x)  # jit-ok: fixture
            u = np.searchsorted(dicts, xs)  # dict-ok: fixture
            return f, u
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src})
    jit = _run(pkg, analysis.RULE_FACTORIES["jit-sites"]()).findings
    dct = _run(pkg, analysis.RULE_FACTORIES["dict-sites"]()).findings
    assert len(jit) == 1 and len(dct) == 1


def test_metric_and_fault_rules_fire_on_seeded_violations(tmp_path):
    src = """
        def record(m):
            m.add_counter("bogus_metric_xyz")
            fault_point("bogus.point.xyz")
    """
    pkg = _pkg(tmp_path, {"fixpkg/mod.py": src})
    metric = _run(pkg, analysis.RULE_FACTORIES["metric-names"]()).findings
    fault = _run(pkg, analysis.RULE_FACTORIES["fault-points"]()).findings
    assert any("bogus_metric_xyz" in f.message for f in metric)
    assert any("bogus.point.xyz" in f.message for f in fault)


# ---------------------------------------------------------------------------
# the tier-1 wiring: whole-package run, runtime budget, CLI modes
# ---------------------------------------------------------------------------


def test_whole_package_analysis_clean_within_budget():
    """dev/analyze.py runs every pass over ballista_tpu/ in ONE process,
    exits 0 with the committed baseline, inside the 10s budget."""
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, ANALYZE,
         "--baseline", os.path.join("dev", "analysis_baseline.json")],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    elapsed = time.perf_counter() - t0
    assert r.returncode == 0, r.stdout + r.stderr
    assert "analysis clean" in r.stdout
    assert elapsed < 10.0, f"analysis took {elapsed:.1f}s (budget 10s)"


def test_production_code_does_not_ask_whether_pytest_is_running():
    """A branch on ``PYTEST_CURRENT_TEST`` makes the tests exercise a
    path users never get (the dense aggregate had one until PR 30).
    ``ballista_tpu/testing/`` is the test-support package and exempt."""
    pkg = os.path.join(REPO, "ballista_tpu")
    hits = []
    for root, dirs, files in os.walk(pkg):
        if os.path.relpath(root, pkg).split(os.sep)[0] == "testing":
            continue
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(root, f)
                if "PYTEST_CURRENT_TEST" in open(path).read():
                    hits.append(os.path.relpath(path, REPO))
    assert not hits, hits


def test_analyze_json_and_changed_only_modes():
    r = subprocess.run(
        [sys.executable, ANALYZE, "--json"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode == 0, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["findings"] == []
    assert payload["baselined"] > 0 and payload["suppressed"] > 0

    r2 = subprocess.run(
        [sys.executable, ANALYZE, "--changed-only"],
        capture_output=True, text=True, cwd=REPO, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr


def test_analyze_exits_nonzero_on_new_finding(tmp_path):
    """A seeded violation in a staged tree fails the driver (and the
    engine loads standalone — no ballista_tpu/__init__ needed)."""
    import shutil

    stage = tmp_path / "repo"
    (stage / "dev").mkdir(parents=True)
    pkg = stage / "ballista_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import numpy as np\n"
        "def unify(dicts):\n"
        "    return np.unique(np.concatenate(dicts))\n")
    shutil.copy(ANALYZE, stage / "dev" / "analyze.py")
    shutil.copytree(os.path.join(REPO, "ballista_tpu", "analysis"),
                    pkg / "analysis",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, str(stage / "dev" / "analyze.py"),
         "--rules", "dict-sites"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 1 and "rogue.py" in r.stderr


# ---------------------------------------------------------------------------
# regression pins for the bugs the first whole-package run surfaced
# ---------------------------------------------------------------------------


def _mkbatch(n=512):
    s = schema(("a", Int64), ("k", Utf8))
    return s, ColumnBatch.from_pydict(s, {
        "a": list(range(n)),
        "k": [f"v{i % 7}" for i in range(n)],
    })


def test_parquet_scan_checks_cancel(tmp_path):
    """io/parquet.py: the batch-emit chunk loop stops at the next
    boundary once the thread's token fires (found by cancel-coverage —
    the loop had no check before ISSUE 13)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": list(range(4000))}), path)
    from ballista_tpu.io.parquet import ParquetSource

    src = ParquetSource(path, batch_capacity=1024)
    token = CancelToken()
    with bind_token(token):
        it = src.scan(0)
        next(it)  # first chunk emits fine
        token.cancel("test")
        with pytest.raises(QueryCancelled):
            next(it)


def test_text_scan_checks_cancel(tmp_path):
    """io/text.py: same boundary, same bug class, text path."""
    from ballista_tpu.io.text import DelimitedSource

    path = str(tmp_path / "t.tbl")
    with open(path, "w") as fh:
        for i in range(4000):
            fh.write(f"{i}|x{i % 5}|\n")
    s = schema(("a", Int64), ("k", Utf8))
    src = DelimitedSource(str(tmp_path), s, "|", trailing_delimiter=True,
                          batch_capacity=1024)
    token = CancelToken()
    with bind_token(token):
        it = src.scan(0)
        next(it)
        token.cancel("test")
        with pytest.raises(QueryCancelled):
            next(it)


def test_ipc_batch_iter_checks_cancel(tmp_path):
    """io/ipc.py: a fired token aborts a partition decode even through
    the shared record-batch iterator (not just the chunk-fed path
    test_spill already pins)."""
    _, b = _mkbatch(2048)
    path = str(tmp_path / "p" / "data.arrow")
    w = ipc.PartitionWriter(path, chunk_bytes=2048)
    w.write_batch(b)
    w.close()
    token = CancelToken()
    token.cancel("test")
    with bind_token(token):
        with pytest.raises(QueryCancelled):
            ipc.read_partition_arrays(path)


def test_batches_from_pieces_checks_cancel(tmp_path):
    """io/ipc.py: shuffle-read assembly (placement + H2D of a group)
    stops between files once cancelled."""
    s, b = _mkbatch(64)
    path = str(tmp_path / "p" / "data.arrow")
    ipc.write_partition(path, [b])
    fp = ipc.read_partition_pieces(path)
    token = CancelToken()
    token.cancel("test")
    with bind_token(token):
        with pytest.raises(QueryCancelled):
            ipc.batches_from_pieces(s, [fp])


def test_dataplane_fetch_checks_cancel(tmp_path):
    """distributed/dataplane.py: a fired token aborts a chunk-stream
    fetch mid-transfer on BOTH framings (streaming and legacy)."""
    from ballista_tpu.distributed import dataplane

    _, b = _mkbatch(2048)
    wd = str(tmp_path / "wd")
    path = dataplane.partition_path(wd, "job1", 1, 0)
    ipc.write_partition(path, [b])
    for stream_serve in (True, False):
        server = dataplane.DataPlaneServer("localhost", 0, wd)
        server.stream_serve = stream_serve
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        try:
            token = CancelToken()
            with bind_token(token):
                it = dataplane.fetch_partition_chunks(
                    "localhost", server.port, "job1", 1, 0,
                    chunk_bytes=1024, window_bytes=2048)
                next(it)  # stream is live
                token.cancel("test")
                with pytest.raises(QueryCancelled):
                    for _chunk in it:
                        pass
        finally:
            server.close()


def test_shuffle_write_sync_is_spanned():
    """io/ipc.py batch_to_arrow: the D2H compaction fetch now runs
    under ONE device.block span (found by sync-span — the shuffle-write
    path synced with zero spans before ISSUE 13), so the profiler's
    device_blocked lane sees shuffle-write sync time."""
    tracing.reconfigure()
    assert tracing.flight_recorder_enabled()
    _, b = _mkbatch(256)
    since = time.time() - 0.5
    ipc.batch_to_arrow(b)
    spans = [r for r in tracing.ring_records(since=since)
             if r.get("name") == "device.block"
             and r.get("site") == "ipc.batch_to_arrow"]
    assert spans, "batch_to_arrow emitted no device.block span"


def test_column_to_numpy_sync_is_spanned():
    """columnar.py to_numpy_logical: result materialization D2H runs
    under a device.block span."""
    tracing.reconfigure()
    _, b = _mkbatch(64)
    since = time.time() - 0.5
    b.columns[0].to_numpy_logical()
    spans = [r for r in tracing.ring_records(since=since)
             if r.get("name") == "device.block"
             and r.get("site") == "column.to_numpy"]
    assert spans, "to_numpy_logical emitted no device.block span"


def test_set_process_identity_first_writer_wins_under_lock():
    """observability/tracing.py: concurrent identity claims settle to
    exactly one role (lock-discipline fix; was a check-then-write race
    on the module-level dict)."""
    saved = dict(tracing._identity)
    tracing._identity.clear()
    try:
        roles = ["executor", "scheduler"] * 8
        threads = [threading.Thread(target=tracing.set_process_identity,
                                    args=(r, f"e{i}"))
                   for i, r in enumerate(roles)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        ident = tracing.process_identity()
        assert ident.get("role") in ("executor", "scheduler")
        assert ident.get("exec", "").startswith("e")
    finally:
        tracing._identity.clear()
        tracing._identity.update(saved)
