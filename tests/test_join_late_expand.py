"""The expanding probe expands AFTER its count is read, at the capacity
the count names.

``JoinExec._probe_expand_stream`` runs two programs around one host read:
``join.ranges`` (per probe row: where each key's matches start and the
running count) and, once the window's totals are on the host,
``join.expand`` (per output slot, at ``bucket_capacity(t)`` slots). What
it yields is already the packed prefix, so nothing compacts it, and a
count over the probe capacity costs one launch, not a re-run.

Every case is held to two references: a NumPy nested-loop join, and the
form this replaced, written out here: ``probe_expand`` at the probe
batch's capacity, ``_assemble_expanded`` at that capacity, a re-run on
overflow, then ``maybe_compact`` with the count.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ballista_tpu import Int64, col, lit, schema
from ballista_tpu.columnar import ColumnBatch
from ballista_tpu.compile import bucket_capacity
from ballista_tpu.compile.governor import governor
from ballista_tpu.io import MemTableSource
from ballista_tpu.kernels import join as join_k
from ballista_tpu.observability import tracing
from ballista_tpu.physical import base
from ballista_tpu.physical import join as join_mod
from ballista_tpu.physical.fusion import fuse_plan
from ballista_tpu.physical.join import JoinExec
from ballista_tpu.physical.operators import FilterExec, ScanExec

CAP = 4096     # probe batch capacity
KEYS = 600     # build keys 0..KEYS-1; probe keys reach a quarter past them


@pytest.fixture(autouse=True)
def cheap_syncs(monkeypatch):
    """maybe_compact stops reading counts for the whole process once one
    read measured slow; a loaded CI host must not decide these tests."""
    monkeypatch.setattr(base, "_SYNC_COST", [0.0])


def _probe_arrays(batches: int, key_shift: int = 0):
    rng = np.random.default_rng(38)
    n = CAP * batches
    return ({"pk": rng.integers(0, KEYS + KEYS // 4, n) + key_shift,
             "f": rng.integers(0, 10000, n), "v": np.arange(n) * 3},
            {"pk": rng.random(n) < 0.9, "v": rng.random(n) < 0.8})


def _probe_source(arrays, validity) -> MemTableSource:
    s = schema(("pk", Int64), ("f", Int64), ("v", Int64))
    n = len(arrays["pk"])
    batches = [
        ColumnBatch.from_numpy(
            s, {c: a[i:i + CAP] for c, a in arrays.items()}, capacity=CAP,
            validity={c: a[i:i + CAP] for c, a in validity.items()})
        for i in range(0, n, CAP)]
    return MemTableSource(s, [batches])


def _build_arrays(copies: int):
    """Every key ``copies`` times (every third once more), in an order
    that is not the sorted one; ``w`` names the row, ``g`` marks the rows
    a build-side filter kills."""
    rng = np.random.default_rng(83)
    k = np.concatenate([np.arange(KEYS)] * copies + [np.arange(KEYS)[::3]])
    k = rng.permutation(k)
    return {"bk": k, "w": np.arange(len(k)) * 7,
            "g": rng.integers(0, 10, len(k))}


def _plan(how: str, keep_pct, dead_build: bool = False, copies: int = 2,
          batches: int = 3, key_shift: int = 0):
    """``keep_pct`` None: nothing fused into the probe side. 1 or 5: the
    fused filter's survivors are compacted BEFORE the probe (``chained``).
    30: the filter runs inside ``join.ranges`` and leaves dead probe
    rows."""
    parrays, pvalid = _probe_arrays(batches, key_shift)
    barrays = _build_arrays(copies)
    bs = schema(("bk", Int64), ("w", Int64), ("g", Int64))
    build = ScanExec("b", MemTableSource.from_pydict(bs, barrays))
    if dead_build:
        build = FilterExec(col("g") < lit(6), build)
    probe = ScanExec("p", _probe_source(parrays, pvalid))
    if keep_pct is not None:
        probe = FilterExec(col("f") < lit(keep_pct * 100), probe)
    join = fuse_plan(JoinExec(build, probe, [("bk", "pk")], how))
    assert isinstance(join, JoinExec)
    assert ("fused probe: Filter" in join.display()) == (keep_pct is not None)
    blive = barrays["g"] < 6 if dead_build else np.ones(len(barrays["bk"]),
                                                        np.bool_)
    return join, (barrays, blive, parrays, pvalid, keep_pct)


def _live_rows(batch: ColumnBatch) -> dict:
    """Every column's values (0 under a null) and validity at the live
    rows, in order."""
    sel = np.asarray(batch.selection)
    assert int(batch.num_rows) == sel.sum()
    out = {}
    for f, c in zip(batch.schema.fields, batch.columns):
        valid = (np.ones(sel.sum(), np.bool_) if c.validity is None
                 else np.asarray(c.validity)[sel])
        out[f.name] = np.where(valid, np.asarray(c.values)[sel], 0)
        out[f.name + "?"] = valid
    return out


def _assert_same_rows(got: dict, want: dict):
    assert got.keys() == want.keys()
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], name)


# -- reference 1: the nested loop ---------------------------------------------


def _nested_loop(data, how: str):
    """What ``execute`` yields, batch for batch, as ``_live_rows`` dicts:
    per probe batch its matches (probe row by probe row, a row's matches
    in key-sorted build order, which a stable sort keeps in row order);
    for left and full then the batch's preserved probe rows without a
    match; for full at the end the build rows nothing matched."""
    barrays, blive, parrays, pvalid, keep_pct = data
    n = len(parrays["pk"])
    sel = np.ones(n, np.bool_) if keep_pct is None else \
        parrays["f"] < keep_pct * 100
    bnames, pnames = ("bk", "w", "g"), ("pk", "f", "v")
    out, hit = [], np.zeros(len(blive), np.bool_)

    def rows(bidx, pidx):
        bidx, pidx = np.asarray(bidx, np.int64), np.asarray(pidx, np.int64)
        d = {}
        for c in bnames:
            ok = bidx >= 0
            d[c] = np.where(ok, barrays[c][np.maximum(bidx, 0)], 0)
            d[c + "?"] = ok
        for c in pnames:
            ok = pidx >= 0
            if c in pvalid:
                ok = ok & pvalid[c][np.maximum(pidx, 0)]
            d[c] = np.where(ok, parrays[c][np.maximum(pidx, 0)], 0)
            d[c + "?"] = ok
        return d

    for start in range(0, n, CAP):
        matches, lonely = [], []
        for i in range(start, start + CAP):
            if not sel[i]:
                continue
            js = np.flatnonzero(blive & (barrays["bk"] == parrays["pk"][i])) \
                if pvalid["pk"][i] else ()
            matches += [(j, i) for j in js]
            hit[list(js)] = True
            if not len(js):
                lonely.append(i)
        out.append(rows([j for j, _ in matches], [i for _, i in matches]))
        if how in ("left", "full"):
            out.append(rows([-1] * len(lonely), lonely))
    if how == "full":
        # build rows a filter killed are not in the build batch at all
        left = np.flatnonzero(blive & ~hit)
        out.append(rows(left, [-1] * len(left)))
    return out


# -- reference 2: the form this replaced --------------------------------------


def _expand_at_capacity(join: JoinExec, partition: int = 0):
    """The expanded batches as the parent made them: one program at the
    probe batch's capacity (every output column gathered at it), re-run
    at the rung of the count on overflow, then compacted."""
    (table, bb, unique, _, mode, key_tables, _, _) = \
        join._materialize_build(partition)
    assert not unique
    tw = join.trace_twin()
    out = []
    for pb, remaps, chained in join._probe_inputs(
            bb, join.probe.execute(partition)):
        def run(table, bb, pb, key_tables, remaps, cap):
            if not chained:
                pb = tw._probe_prologue(pb)
            pkeys, plive = tw._probe_keys(pb, mode, key_tables, remaps)
            prows, brows, olive, total = join_k.probe_expand(
                table, pkeys, plive, cap)
            return tw._assemble_expanded(bb, pb, prows, brows, olive), total

        cap = pb.capacity
        while True:
            batch, total = jax.jit(run, static_argnums=5)(
                table, bb, pb, key_tables, remaps, cap)
            if int(total) <= cap:
                break
            cap = bucket_capacity(int(total))
        out.append(base.maybe_compact(batch, known_rows=int(total)))
    return out


# -- the operator -------------------------------------------------------------


@pytest.mark.parametrize("dead_build", [False, True],
                         ids=["build-whole", "build-dead-rows"])
@pytest.mark.parametrize("keep_pct", [None, 30, 1],
                         ids=["no-chain", "chain-inside", "chained"])
@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_late_expansion_equals_both_references(how, keep_pct, dead_build):
    join, data = _plan(how, keep_pct, dead_build)
    got = list(join.execute(0))
    want = _nested_loop(data, how)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        _assert_same_rows(_live_rows(g), w)
    assert sum(int(b.num_rows) for b in got) > 0
    if how == "full":
        return  # its probe batches do not come through _probe_inputs
    step = 2 if how == "left" else 1
    old = _expand_at_capacity(_plan(how, keep_pct, dead_build)[0])
    assert len(old) == len(got[::step])
    for g, o in zip(got[::step], old):
        _assert_same_rows(_live_rows(g), _live_rows(o))
        assert g.schema == o.schema
        # a packed prefix: live rows first, at the rung of their count
        n = int(g.num_rows)
        np.testing.assert_array_equal(np.asarray(g.selection),
                                      np.arange(g.capacity) < n)
        assert g.capacity == bucket_capacity(n)
        if o.capacity * 4 <= CAP:  # where the parent compacted: its rung
            assert g.capacity == o.capacity


def _count(totals, name):
    return totals.get(name, {"count": 0})["count"]


def _calls(namespace):
    return sum(r["calls"] for r in governor().entry_rows()
               if r["namespace"] == namespace)


def _events(name, since):
    return [r for r in tracing.ring_records(since=since)
            if r.get("name") == name]


@pytest.mark.parametrize("how", ["inner", "left"])
def test_no_match_at_all_yields_an_empty_batch_at_the_floor(how):
    t0 = time.time()
    join, data = _plan(how, None, key_shift=10 * KEYS)
    got = list(join.execute(0))
    step = 2 if how == "left" else 1
    assert len(got) == 3 * step
    for b in got[::step]:
        assert int(b.num_rows) == 0 and not np.asarray(b.selection).any()
        assert b.capacity == bucket_capacity(0)
        assert b.schema == join.output_schema()
    for g, w in zip(got, _nested_loop(data, how)):
        _assert_same_rows(_live_rows(g), w)
    events = _events("join.expand", t0)
    assert [(e["rows"], e["probes"], e["to"]) for e in events] == \
        [(0, CAP, bucket_capacity(0))] * 3


@pytest.mark.parametrize("keep_pct", [None, 5], ids=["no-chain", "chained"])
def test_blow_up_over_the_probe_capacity_is_one_launch(keep_pct):
    """40 build rows a key: a batch's matches are many times its
    capacity. One ``join.ranges`` and one ``join.expand`` launch a batch,
    at the rung of the count; nothing is truncated and nothing re-run."""
    join, data = _plan("inner", keep_pct, copies=40, batches=2)
    ranges, expands, t0 = _calls("join.ranges"), _calls("join.expand"), \
        time.time()
    got = list(join.execute(0))
    assert _calls("join.ranges") - ranges == 2
    assert _calls("join.expand") - expands == 2
    want = _nested_loop(data, "inner")
    probes = CAP if keep_pct is None else bucket_capacity(CAP // 20)
    for g, w, e in zip(got, want, _events("join.expand", t0)):
        t = len(w["pk"])
        assert t > probes
        assert g.capacity == bucket_capacity(t) and int(g.num_rows) == t
        _assert_same_rows(_live_rows(g), w)
        assert (e["rows"], e["probes"], e["to"]) == (t, probes, g.capacity)
    old = _expand_at_capacity(_plan("inner", keep_pct, copies=40,
                                    batches=2)[0])
    for g, o in zip(got, old):
        assert g.capacity == o.capacity
        _assert_same_rows(_live_rows(g), _live_rows(o))


@pytest.mark.parametrize("batches,reads", [(3, 1), (8, 1), (9, 2), (20, 3)])
def test_one_count_read_a_window_of_eight(batches, reads):
    join, _ = _plan("inner", None, batches=batches)
    before, t0 = tracing.span_totals(), time.time()
    got = list(join.execute(0))
    after = tracing.span_totals()
    assert len(got) == batches
    blocks = [r for r in _events("device.block", t0)
              if r.get("site") == "join.expand_totals"]
    assert len(blocks) == reads
    assert sum(r["n"] for r in blocks) == batches
    assert max(r["n"] for r in blocks) <= join_mod._SYNC_WINDOW
    for name in ("join.expand", "join.search"):
        assert _count(after, name) - _count(before, name) == batches
    # the expanded batch is the packed prefix: nothing searches for it
    assert _count(after, "compact.search") == \
        _count(before, "compact.search")


def test_window_flushes_early_on_bytes(monkeypatch):
    """A pending batch pins its probe columns and two int32 a row; the
    window's byte bound flushes before eight batches where that is
    much."""
    row = 3 * 8 + 8  # pk, f, v and the two range vectors
    monkeypatch.setattr(join_mod, "_SYNC_WINDOW_BYTES", 2 * CAP * row)
    join, data = _plan("inner", None, batches=5)
    t0 = time.time()
    got = list(join.execute(0))
    blocks = [r["n"] for r in _events("device.block", t0)
              if r.get("site") == "join.expand_totals"]
    assert blocks == [2, 2, 1]
    for g, w in zip(got, _nested_loop(data, "inner")):
        _assert_same_rows(_live_rows(g), w)


def test_a_probe_compacted_before_is_not_compacted_after():
    """The one compaction a selective chain's batch gets is the one
    BEFORE its probe; each launch counts one ``join.expand`` event whose
    ``probes`` is the compacted capacity."""
    join, _ = _plan("left", 1)
    before, t0 = tracing.span_totals(), time.time()
    got = list(join.execute(0))
    after = tracing.span_totals()
    assert len(got) == 6
    assert _count(after, "compact.search") - \
        _count(before, "compact.search") == 3
    assert _count(after, "join.probe_compacted") - \
        _count(before, "join.probe_compacted") == 3
    events = _events("join.expand", t0)
    assert len(events) == 3
    for e, b in zip(events, got[::2]):
        assert e["probes"] == bucket_capacity(CAP // 100)
        assert e["rows"] == int(b.num_rows) and e["to"] == b.capacity


def test_no_learned_capacity_and_no_rerun_counter():
    """The state the overflow loop kept is gone with the loop."""
    from ballista_tpu.observability.registry import OPERATOR_METRICS

    join, _ = _plan("inner", None, copies=40, batches=1)
    list(join.execute(0))
    assert not hasattr(join, "_expand_cap_floor")
    assert "expand_reruns" not in OPERATOR_METRICS
    assert "expand_reruns" not in join.metrics().values()


# -- the kernels --------------------------------------------------------------


@pytest.mark.parametrize("live_share", [1.0, 0.4, 0.0])
@pytest.mark.parametrize("nb,npr", [(40, 64), (700, 512), (40_000, 2048)])
def test_halves_composed_equal_probe_expand(nb, npr, live_share):
    """``probe_ranges`` then ``expand_slots`` at the rung of the count
    against ``probe_expand`` at a capacity that holds everything, slot
    for slot; past the capacity, cut off like it."""
    rng = np.random.default_rng(nb + npr)
    domain = max(4, nb // 3)
    bkeys = jnp.asarray(rng.integers(0, domain, nb))
    blive = jnp.asarray(rng.random(nb) < 0.8)
    pkeys = jnp.asarray(rng.integers(0, domain + domain // 4, npr))
    plive = jnp.asarray(rng.random(npr) < live_share)
    table = jax.jit(join_k.build_lookup)(bkeys, blive)
    lo, ends, total = jax.jit(join_k.probe_ranges)(table, pkeys, plive)
    t = int(total)
    assert lo.dtype == ends.dtype == jnp.int32 and lo.shape == ends.shape
    assert t == int(ends[-1]) and (t == 0) == (live_share == 0.0)
    big = bucket_capacity(8 * npr)
    want = jax.device_get(jax.jit(join_k.probe_expand, static_argnums=3)(
        table, pkeys, plive, big))
    assert want[3] == t <= big
    for cap in (bucket_capacity(t), max(8, t // 2)):
        prows, brows, olive = jax.device_get(
            jax.jit(join_k.expand_slots, static_argnums=4)(
                table, lo, ends, total, cap))
        kept = min(t, cap)
        np.testing.assert_array_equal(olive, np.arange(cap) < kept)
        np.testing.assert_array_equal(prows[:kept], want[0][:kept])
        np.testing.assert_array_equal(brows[:kept], want[1][:kept])
        assert not prows[kept:].any() and not brows[kept:].any()
