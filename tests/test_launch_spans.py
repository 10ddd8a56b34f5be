"""Launches by program and blocked reads by site, counted where they happen.

Every call through ``GovernedFunction.call_with`` is a ``launch`` span whose
site is the program's name as a device trace prints it: tallied in
``tracing.span_totals()`` under ``launch:jit_<family>`` (``launch.cold:*``
when the call compiled), annotated in a profiler trace, never a ring
record. A span's ``site`` is its sub-key in the totals, for every span that
carries one; the sub-keys of a name sum to the name.
"""

import os
import textwrap

import jax.numpy as jnp
import pytest

from ballista_tpu import analysis
from ballista_tpu.compile.governor import (
    compile_stats,
    governed,
    program_name,
)
from ballista_tpu.observability import tracing
from ballista_tpu.observability.tracing import (
    ring_records,
    span_totals,
    trace_event,
    trace_span,
)


def _delta(before: dict, after: dict) -> dict:
    """Totals keys the interval moved: ``{key: (count, seconds)}``."""
    zero = {"count": 0, "seconds": 0.0}
    return {k: (t["count"] - before.get(k, zero)["count"],
                t["seconds"] - before.get(k, zero)["seconds"])
            for k, t in after.items()
            if t["count"] != before.get(k, zero)["count"]}


def _family(delta: dict, prefix: str) -> int:
    return sum(c for k, (c, _) in delta.items() if k.startswith(prefix))


@pytest.fixture()
def program():
    """A governed program no other test shares, its key and its site."""
    key = ("launchtest.add_one", os.urandom(4).hex())
    return governed(key, lambda: lambda x: x + 1), "jit_" + program_name(key)


def test_a_launch_is_tallied_under_its_programs_name_and_kept_out_of_the_ring(
        program):
    fn, site = program
    assert site == "jit_launchtest_add_one" == fn.program
    x = jnp.arange(8)
    fn(x)  # compiles
    before, t0 = span_totals(), ring_records()
    for _ in range(3):
        fn(x)
    moved = _delta(before, span_totals())
    assert moved["launch:" + site][0] == 3 and moved["launch"][0] == 3
    assert moved["launch:" + site][1] == pytest.approx(moved["launch"][1])
    assert moved["launch"][1] > 0
    assert not any(k.startswith("launch.cold") for k in moved)
    new = ring_records()[len(t0):]
    assert not [r for r in new if str(r["name"]).startswith("launch")]


def test_a_compiling_first_call_is_a_cold_launch(program):
    fn, site = program
    before = span_totals()
    fn(jnp.arange(8))
    moved = _delta(before, span_totals())
    assert moved["launch.cold:" + site][0] == 1
    assert "launch:" + site not in moved and "launch" not in moved
    # the compile's own record stays what it was
    assert moved["compile.jit"][0] == 1
    # a new shape compiles again: cold again, then warm
    before = span_totals()
    fn(jnp.arange(16))
    fn(jnp.arange(16))
    moved = _delta(before, span_totals())
    assert moved["launch.cold:" + site][0] == 1
    assert moved["launch:" + site][0] == 1


def test_both_families_counts_sum_to_governed_calls(program):
    fn, _ = program
    before, calls = span_totals(), compile_stats()["governed_calls"]
    for n in (8, 8, 16, 8, 16):
        fn(jnp.arange(n))
    moved = _delta(before, span_totals())
    assert compile_stats()["governed_calls"] - calls == 5
    assert _family(moved, "launch:") + _family(moved, "launch.cold:") == 5
    assert moved["launch"][0] + moved["launch.cold"][0] == 5


def test_a_failing_launch_is_still_counted():
    key = ("launchtest.raises", os.urandom(4).hex())

    def build():
        def run(x):
            raise ValueError("no program")
        return run

    before, calls = span_totals(), compile_stats()["governed_calls"]
    with pytest.raises(ValueError):
        governed(key, build)(jnp.arange(4))
    moved = _delta(before, span_totals())
    assert compile_stats()["governed_calls"] - calls == 1
    assert _family(moved, "launch:") + _family(moved, "launch.cold:") == 1


@pytest.mark.parametrize("emit", ["span", "event"])
def test_the_sites_of_a_name_sum_to_the_name(emit):
    name = "sitetest." + emit
    before = span_totals()
    for site in ("a.one", "a.one", "b.two"):
        if emit == "span":
            with trace_span(name, site=site, rows=3):
                pass
        else:
            trace_event(name, site=site, rows=3)
    moved = _delta(before, span_totals())
    assert moved[name][0] == 3
    assert moved[name + ":a.one"][0] == 2 and moved[name + ":b.two"][0] == 1
    assert moved[name][1] == pytest.approx(
        moved[name + ":a.one"][1] + moved[name + ":b.two"][1])
    # the ring record keeps the plain name, the site as an attribute
    kept = [r for r in ring_records() if r["name"] == name]
    assert [r["site"] for r in kept[-3:]] == ["a.one", "a.one", "b.two"]


@pytest.mark.parametrize("emit", ["span", "event"])
def test_a_span_with_no_site_makes_no_second_key(emit):
    name = "nositetest." + emit
    before = span_totals()
    if emit == "span":
        with trace_span(name, rows=3):
            pass
    else:
        trace_event(name, rows=3)
    assert list(_delta(before, span_totals())) == [name]


def test_the_totals_count_with_the_flight_recorder_off(monkeypatch, program):
    fn, site = program
    fn(jnp.arange(8))
    monkeypatch.setenv("BALLISTA_FLIGHT_RECORDER", "0")
    tracing.reconfigure()
    try:
        assert not tracing.flight_recorder_enabled()
        before = span_totals()
        fn(jnp.arange(8))
        with trace_span("device.block", site="launchtest.off"):
            pass
        moved = _delta(before, span_totals())
        assert moved["launch:" + site][0] == 1
        assert moved["device.block:launchtest.off"][0] == 1
        assert ring_records() == []
    finally:
        monkeypatch.delenv("BALLISTA_FLIGHT_RECORDER")
        tracing.reconfigure()


def test_a_span_that_is_never_recorded_takes_no_span_id():
    """What is emitted inside a launch hangs from the enclosing recorded
    span, not from a parent no record holds."""
    with trace_span("launchtest.outer") as outer:
        quiet = trace_span("launch", site="jit_launchtest_quiet")
        quiet.record = False
        with quiet:
            trace_event("launchtest.inner")
    inner = [r for r in ring_records() if r["name"] == "launchtest.inner"][-1]
    assert inner["psid"] == outer._sid


# -- a warm TPC-H q3: the ring reads what it read, the totals say more ------


@pytest.fixture(scope="module")
def q3():
    import tempfile

    from benchmarks.tpch import datagen
    from benchmarks.tpch.schema_def import register_tpch
    from ballista_tpu.client import BallistaContext

    with tempfile.TemporaryDirectory() as data_dir:
        datagen.generate(data_dir, scale=0.002, num_parts=2)
        ctx = BallistaContext.standalone()
        register_tpch(ctx, data_dir, "tbl")
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "..", "benchmarks", "tpch", "queries",
                               "q3.sql")) as fh:
            text = fh.read()
        for _ in range(2):  # compile, then let the plan settle
            ctx.sql(text).collect()
        yield ctx, text


def test_a_warm_q3_blocks_as_often_in_the_totals_as_in_the_ring(q3):
    import time

    ctx, text = q3
    before, calls = span_totals(), compile_stats()["governed_calls"]
    started = time.time()
    ctx.sql(text).collect()
    moved = _delta(before, span_totals())
    # the parent's rule (perfbench/metrics/syncs_per_query.py): device.block
    # records in the ring since the query started
    in_ring = [r for r in ring_records(since=started)
               if r.get("name") == "device.block"]
    assert moved["device.block"][0] == len(in_ring) > 0
    assert _family(moved, "device.block:") == len(in_ring)
    by_site = {}
    for r in in_ring:
        by_site[r["site"]] = by_site.get(r["site"], 0) + 1
    assert by_site == {k.split(":", 1)[1]: c for k, (c, _) in moved.items()
                       if k.startswith("device.block:")}
    assert sum(s for k, (_, s) in moved.items()
               if k.startswith("device.block:")) == pytest.approx(
        moved["device.block"][1])
    # every launch of the query is counted, by program, and none is a record
    launched = compile_stats()["governed_calls"] - calls
    assert launched > 0 and "launch.cold" not in moved
    assert _family(moved, "launch:") == moved["launch"][0] == launched
    assert all(k.startswith("launch:jit_") for k in moved
               if k.startswith("launch:"))
    assert not [r for r in ring_records(since=started)
                if str(r.get("name")).startswith("launch")]


# -- the sync-span pass holds a device.block's site to a literal -------------


@pytest.mark.parametrize("site,findings", [
    ('site="fix.read"', 0),
    ("site=where", 1),
    ('site="fix." + where', 1),
    ("rows=3", 1),
])
def test_sync_span_asks_for_a_literal_site(tmp_path, site, findings):
    src = f"""
        import numpy as np
        from .tr import trace_span

        def read(col, where):
            with trace_span("device.block", {site}):
                return np.asarray(col.values)
    """
    files = {"fixpkg/mod.py": src,
             "fixpkg/tr.py": ("from contextlib import contextmanager\n"
                              "@contextmanager\n"
                              "def trace_span(name, **kw):\n    yield\n")}
    for rel, body in files.items():
        path = tmp_path / "fixroot" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(body))
    pkg = analysis.Package.load(str(tmp_path / "fixroot"),
                                package_rel="fixpkg")
    res = analysis.analyze(pkg, [analysis.RULE_FACTORIES["sync-span"]()],
                           None)
    assert len(res.findings) == findings, [f.message for f in res.findings]
    assert all("string literal" in f.message for f in res.findings)
