"""A join compacts a probe batch BEFORE it probes when the filter fused
into its probe side kills most of the rows.

``fuse_plan`` pulls a FilterExec off the probe side into
``JoinExec.probe_chain``. ``JoinExec._probe_inputs`` then runs that chain
alone, counts what it kept and, under the rule ``PipelineOp.execute``
follows (``PhysicalPlan.compact_learning``), compacts a batch under a
quarter full before the probe; a chain that does not shrink its batches
declines twice and from then on takes the single fused program with no
count read. Every case holds the compacted path to the fused one (the
same plan with the rule made to decline from the start), batch by batch,
live row by live row, in order.
"""

import numpy as np
import pytest

from ballista_tpu import Int64, Utf8, col, lit, schema
from ballista_tpu.columnar import ColumnBatch, Dictionary
from ballista_tpu.compile.governor import governor
from ballista_tpu.io import MemTableSource
from ballista_tpu.observability import tracing
from ballista_tpu.physical import base
from ballista_tpu.physical.fusion import fuse_plan
from ballista_tpu.physical.join import JoinExec
from ballista_tpu.physical.operators import FilterExec, ScanExec

CAP = 16384      # probe batch capacity: 1% of it lands on the 1,024 rung
BATCHES = 4
KEYS = 3000      # build keys 0..KEYS-1; probe keys reach past them


@pytest.fixture(autouse=True)
def cheap_syncs(monkeypatch):
    """maybe_compact stops reading counts for the whole process once one
    read measured slow; a loaded CI host must not decide these tests."""
    monkeypatch.setattr(base, "_SYNC_COST", [0.0])


def _probe_source(kind: str, dense_first: bool = False) -> MemTableSource:
    """One partition of BATCHES full batches: the key (by ``kind``), ``f``
    uniform over 0..9999 for the filter, ``v`` a payload with nulls. With
    ``dense_first`` the first batch holds ten times the rows under
    ``f < 100`` that the others hold."""
    rng = np.random.default_rng(32)
    n = CAP * BATCHES
    k = rng.integers(0, KEYS + KEYS // 4, n)
    arrays = {"f": rng.integers(0, 10000, n), "v": np.arange(n) * 3}
    if dense_first:
        arrays["f"][:CAP][arrays["f"][:CAP] < 1000] = 0
    validity = {"v": rng.random(n) < 0.9}
    dicts = {}
    if kind == "utf8":
        fields = [("pk", Utf8)]
        dicts["pk"], arrays["pk"] = Dictionary.encode(
            ["key%05d" % x for x in k])
    elif kind == "packed":
        fields = [("pk", Int64), ("pk2", Int64)]
        arrays["pk"], arrays["pk2"] = k // 50, k % 50
    else:
        fields = [("pk", Int64)]
        arrays["pk"] = k
        if kind == "nullable":
            validity["pk"] = rng.random(n) < 0.8
    s = schema(*fields, ("f", Int64), ("v", Int64))
    batches = [
        ColumnBatch.from_numpy(
            s, {c: a[i * CAP:(i + 1) * CAP] for c, a in arrays.items()},
            dictionaries=dicts, capacity=CAP,
            validity={c: a[i * CAP:(i + 1) * CAP]
                      for c, a in validity.items()})
        for i in range(BATCHES)]
    return MemTableSource(s, [batches])


def _build_source(kind: str, duplicates: bool) -> MemTableSource:
    k = np.arange(KEYS)
    if duplicates:  # every third key twice: the expanding probe
        k = np.concatenate([k, k[::3]])
    data = {"w": np.arange(len(k)) * 7}
    if kind == "utf8":
        fields = [("bk", Utf8)]
        data["bk"] = ["key%05d" % x for x in k]
    elif kind == "packed":
        fields = [("bk", Int64), ("bk2", Int64)]
        data["bk"], data["bk2"] = k // 50, k % 50
    else:
        fields = [("bk", Int64)]
        data["bk"] = k
    s = schema(*fields, ("w", Int64))
    return MemTableSource.from_pydict(s, data)


def _plan(how, keep_pct, kind="int", null_aware=False, duplicates=False,
          probe=None) -> JoinExec:
    on = [("bk", "pk")] + ([("bk2", "pk2")] if kind == "packed" else [])
    probe = probe or _probe_source(kind)
    join = fuse_plan(JoinExec(
        ScanExec("b", _build_source(kind, duplicates)),
        FilterExec(col("f") < lit(keep_pct * 100), ScanExec("p", probe)),
        on, how, null_aware=null_aware))
    assert isinstance(join, JoinExec) and "fused probe: Filter" in \
        join.display()
    return join


def _declining(join: JoinExec) -> JoinExec:
    """The parent's path: the rule has already declined twice."""
    join._compact_misses = 2
    return join


def _live_rows(batch: ColumnBatch) -> dict:
    """Every column's values and validity at the live rows, in order."""
    sel = np.asarray(batch.selection)
    assert int(batch.num_rows) == sel.sum()
    out = {}
    for f, c in zip(batch.schema.fields, batch.columns):
        out[f.name] = np.asarray(c.values)[sel]
        if c.validity is not None:
            out[f.name + "?"] = np.asarray(c.validity)[sel]
        if c.dictionary is not None:
            out[f.name + "#"] = c.dictionary.lookup(out[f.name])
    return out


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.schema == w.schema
        rows_g, rows_w = _live_rows(g), _live_rows(w)
        assert rows_g.keys() == rows_w.keys()
        for name in rows_w:
            np.testing.assert_array_equal(rows_g[name], rows_w[name], name)


def _count(totals, name):
    return totals.get(name, {"count": 0})["count"]


def _calls(namespace):
    return sum(r["calls"] for r in governor().entry_rows()
               if r["namespace"] == namespace)


class _Reads:
    """Counts maybe_compact's blocking count reads (calls that are not
    handed ``known_rows``)."""

    def __init__(self, monkeypatch):
        self.n = 0
        inner = base.maybe_compact

        def counted(batch, *a, **kw):
            self.n += kw.get("known_rows") is None
            return inner(batch, *a, **kw)

        monkeypatch.setattr(base, "maybe_compact", counted)


HOWS = [("inner", False), ("left", False), ("semi", False),
        ("anti", False), ("anti", True)]


@pytest.mark.parametrize("keep_pct", [0, 1, 30, 100])
@pytest.mark.parametrize("how,null_aware", HOWS,
                         ids=[h + ("-null-aware" if n else "")
                              for h, n in HOWS])
def test_compacted_probe_equals_fused_probe(how, null_aware, keep_pct):
    # a nullable probe key: NOT IN must drop its NULLs either way
    kind = "nullable" if null_aware else "int"
    got = list(_plan(how, keep_pct, kind, null_aware).execute(0))
    want = list(_declining(
        _plan(how, keep_pct, kind, null_aware)).execute(0))
    _assert_same_batches(got, want)
    if keep_pct in (0, 1):
        # probed at the survivors' rung, not at the scan's capacity
        assert {b.capacity for b in got} == {1024}
    if keep_pct == 100 and how == "left":
        assert {b.capacity for b in got} == {CAP}


@pytest.mark.parametrize("keep_pct", [1, 100])
@pytest.mark.parametrize("how", ["inner", "left"])
def test_expanding_probe_equals_fused_probe(how, keep_pct):
    """Duplicate build keys: the expanding probe (and the left join's
    unmatched batches) take their batches from the same place."""
    got = list(_plan(how, keep_pct, duplicates=True).execute(0))
    want = list(_declining(
        _plan(how, keep_pct, duplicates=True)).execute(0))
    assert len(got) == BATCHES * (2 if how == "left" else 1)
    _assert_same_batches(got, want)


@pytest.mark.parametrize("kind", ["nullable", "utf8", "packed"])
@pytest.mark.parametrize("how", ["inner", "left"])
def test_key_forms_survive_compaction(kind, how):
    """A probe key with validity; a utf8 key, whose codes are remapped
    into the build's dictionary from the RAW batch's dictionary; a packed
    two-column key."""
    got = list(_plan(how, 1, kind).execute(0))
    want = list(_declining(_plan(how, 1, kind)).execute(0))
    _assert_same_batches(got, want)
    assert sum(int(b.num_rows) for b in got) > 0
    assert {b.capacity for b in got} == {1024}


def test_selective_chain_counts_one_event_and_one_read_a_batch(monkeypatch):
    reads = _Reads(monkeypatch)
    join = _plan("inner", 1)
    before, chains = tracing.span_totals(), _calls("join.prologue")
    out = list(join.execute(0))
    after = tracing.span_totals()
    assert len(out) == BATCHES
    assert _count(after, "join.probe_compacted") - \
        _count(before, "join.probe_compacted") == BATCHES
    assert _count(after, "join.probe_fused") == \
        _count(before, "join.probe_fused")
    assert _count(after, "compact.search") - \
        _count(before, "compact.search") == BATCHES
    assert _calls("join.prologue") - chains == BATCHES
    # one count read a batch, before the probe; none after it
    assert reads.n == BATCHES
    event = [r for r in tracing.ring_records()
             if r.get("name") == "join.probe_compacted"][-1]
    assert event["capacity"] == CAP and event["to"] == 1024
    assert 0 < event["rows"] < 1024


def test_unselective_chain_declines_twice_then_only_fuses(monkeypatch):
    reads = _Reads(monkeypatch)
    join = _plan("left", 100)
    before, chains = tracing.span_totals(), _calls("join.prologue")
    out = list(join.execute(0))
    after = tracing.span_totals()
    assert len(out) == BATCHES
    assert _calls("join.prologue") - chains == 2
    assert _count(after, "join.probe_fused") - \
        _count(before, "join.probe_fused") == BATCHES
    assert _count(after, "join.probe_compacted") == \
        _count(before, "join.probe_compacted")
    assert not join.still_compacting()
    # two learning reads, and the one after each probe that the parent makes
    assert reads.n == 2 + BATCHES
    # a second execution (another partition, a cached plan) asks nothing
    reads.n, chains = 0, _calls("join.prologue")
    list(join.execute(0))
    assert _calls("join.prologue") == chains
    assert reads.n == BATCHES


def test_compacted_batches_land_on_one_rung():
    """The first batch keeps ~1,600 rows (the 2,048 rung), the later ones
    ~160: the learned floor holds them on the first batch's rung, so the
    probe and everything downstream compile for one shape."""
    src = _probe_source("int", dense_first=True)
    got = list(_plan("inner", 1, probe=src).execute(0))
    assert int(got[0].num_rows) > 1024
    assert [b.capacity for b in got] == [2048] * BATCHES
    assert all(int(b.num_rows) < 1024 for b in got[1:])
    want = list(_declining(_plan("inner", 1, probe=src)).execute(0))
    _assert_same_batches(got, want)
