"""The readers ``standalone-join-sf10`` brings: the cell and its files are
found by name; the counter and span readers on hand-made records and
snapshots; the take's roofline share against a hand-made trace;
``repart_bytes`` against generated data; and that a program without the
key, the span or the event, or a run without a device plane, gives every
one of them nothing to read."""

import time

import pytest

import run

MS = 1_000_000  # nanoseconds
CELL = "standalone-join-sf10"
NEW = ("table_cache_resident_gb", "scanned_rows_share",
       "join_builds_per_query", "repart_rows_per_query",
       "repart_take_roofline_share")


def test_the_cell_and_its_files_are_found_by_name():
    cell = run.find_cell(CELL)
    assert cell["chips"] == 1 and cell["config"]["scale"] == 10.0
    assert cell["traffic"]["round"] == ["q3", "q14"]
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"]
              if m.get("workloads") == [CELL]]
    assert listed == cell["reports"]["per_layer"] and set(NEW) < set(listed)
    for name in listed + cell["reports"]["end_to_end"]:
        assert hasattr(run.load_reader(name), "read")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "tpch-sf10-standalone")
    assert entry["source"] == cell["config"]["source"]
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["scale"]


def shared_helper():
    """``metrics/_plan_events.py`` as the readers import it."""
    run.load_reader("repart_rows_per_query")  # puts metrics/ on the path
    import _plan_events

    return _plan_events


@pytest.fixture
def events_known(monkeypatch):
    monkeypatch.setattr(shared_helper(), "known", lambda event: True)


def window(per_query, reader):
    """A window whose queries kept ``{"scan.serve": [...], "repart.take":
    [...]}`` under ``reader``."""
    return {"window": {"queries": [
        {"query": q, "started": 0.0, "seconds": 1.0,
         "record": {"phases": {}, "readers": {reader: {
             "scan.serve": scans, "repart.take": takes}}}}
        for q, scans, takes in per_query]}}


def scan(table, rows):
    return {"table": table, "rows": rows, "batches": 1, "how": "resident"}


def take(side, rows):
    return {"side": side, "rows": rows, "pieces": 8, "capacity": 1 << 20}


def test_rows_the_repartitions_gathered_a_query(events_known):
    reader = run.load_reader("repart_rows_per_query")
    q3 = [take("customer+orders", 700), take("customer+orders", 300)]
    obs = window([("q3", [], q3), ("q14", [], []), ("q3", [], q3),
                  ("q14", [], [])], "repart_rows_per_query")
    assert reader.read(obs) == pytest.approx(500.0)
    assert reader.after_query(None, time.time(), 1.0) == \
        {"scan.serve": [], "repart.take": []}  # none since that moment


def test_scanned_rows_share_counts_against_the_files(events_known, tmp_path):
    import datagen

    data_dir = str(tmp_path)
    tables = ["customer", "orders", "lineitem", "part"]
    datagen.generate(data_dir, 0.01, tables, 4, 7)
    reader = run.load_reader("scanned_rows_share")
    n = {t: reader.table_rows(data_dir, t) for t in tables}
    assert n["part"] == datagen.table("part").rows(0.01) == 2000
    obs = {"cell": run.find_cell(CELL), "data_dir": data_dir,
           **window([("q3", [], []),
                     ("q14", [scan("part", 1500), scan("part", 500)], [])],
                    "scanned_rows_share")}
    q3_reads = n["customer"] + n["orders"] + n["lineitem"]
    assert reader.read(obs) == pytest.approx(
        100.0 * 2000 / (q3_reads + n["lineitem"] + n["part"]))
    # a scan whose source keeps no count says None: it adds no row
    obs["window"]["queries"][0]["record"]["readers"]["scanned_rows_share"][
        "scan.serve"] = [scan("orders", None)]
    assert 0 < reader.read(obs) < 2.0


@pytest.mark.parametrize("name", ["scanned_rows_share",
                                  "repart_rows_per_query",
                                  "repart_take_roofline_share"])
def test_a_program_without_the_event_gives_nothing(name, monkeypatch):
    monkeypatch.setattr(shared_helper(), "known", lambda event: False)
    reader = run.load_reader(name)
    obs = {"cell": run.find_cell(CELL), "planes": one_chip(),
           "device": {"kind": "TPU v5 lite"},
           **window([("q3", [], []), ("q14", [], [])], name)}
    assert reader.read(obs) is None


def test_snapshot_readers_and_a_parent_without_their_keys():
    builds = run.load_reader("join_builds_per_query")
    queries = {"window": {"queries": [{}] * 4}}
    assert builds.read({"snapshots": {"join_builds_per_query": (12, 12)},
                        **queries}) == 0.0
    assert builds.read({"snapshots": {"join_builds_per_query": (12, 14)},
                        **queries}) == 0.5
    assert builds.read({"snapshots": {"join_builds_per_query": (None, None)},
                        **queries}) is None
    resident = run.load_reader("table_cache_resident_gb")
    assert resident.read({"snapshots": {"table_cache_resident_gb": (
        3_760_000_000, 3_760_000_000)}}) == pytest.approx(3.76)
    assert resident.read(
        {"snapshots": {"table_cache_resident_gb": (None, None)}}) is None
    # against the program itself: the counter is there, and the span's
    # count is read once a build has been made
    assert resident.snapshot() >= 0
    from ballista_tpu.observability import tracing

    if "join.build" not in tracing.span_totals():
        assert builds.snapshot() is None
    with tracing.trace_span("join.build", side="t"):
        pass
    assert builds.snapshot() >= 1


def one_chip(take_ms=(2, 3), other_ms=5):
    """One traced q3 and one q14, 100 ms each: ``jit_repart_take`` runs
    ``take_ms`` inside q3, another program beside it; a third take lies
    after the traced window and is not counted."""
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["collect:q3", 0, 100 * MS], ["collect:q14", 100 * MS, 100 * MS]]}]}
    modules = [[f"jit_repart_take({k})", (10 + 10 * k) * MS, ms * MS]
               for k, ms in enumerate(take_ms)]
    modules += [["jit_join_ranges(9)", 50 * MS, other_ms * MS],
                ["jit_repart_take(7)", 300 * MS, 4 * MS],
                ["jit_repart_take_more(8)", 60 * MS, 4 * MS]]
    ops = [["gather.1", s, d] for _, s, d in modules]
    return [host, {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]


def test_repart_bytes_and_the_take_roofline_share(events_known, tmp_path):
    import datagen
    import repart_bytes

    data_dir = str(tmp_path)
    datagen.generate(data_dir, 0.01, ["customer", "orders", "lineitem",
                                      "part"], 4, 7)
    cell = run.find_cell(CELL)
    q3, q14 = cell["queries"]["q3"], cell["queries"]["q14"]
    # money and keys 8 bytes, dates and dictionary codes 4
    assert repart_bytes.row_bytes(q3, data_dir, "lineitem") == 8 + 8 + 8 + 4
    assert repart_bytes.row_bytes(q3, data_dir, "customer+orders") == \
        (8 + 4) + (8 + 8 + 4 + 4)
    assert repart_bytes.row_bytes(q14, data_dir, "part") == 8 + 4
    with pytest.raises(KeyError):
        repart_bytes.row_bytes(q14, data_dir, "orders")
    takes = [take("customer+orders", 700), take("customer+orders", 300)]
    assert repart_bytes.take_bytes(q3, data_dir, takes) == 1000 * 36 * 2
    assert repart_bytes.take_bytes(q14, data_dir, []) == 0

    reader = run.load_reader("repart_take_roofline_share")
    name = "repart_take_roofline_share"
    obs = {"cell": cell, "planes": one_chip(), "data_dir": data_dir,
           "device": {"kind": "TPU v5 lite"},
           "peaks": run.read_json(run.HERE, "peaks.json"),
           **window([("q3", [], takes), ("q14", [], []), ("q3", [], takes),
                     ("q14", [], [])], name)}
    # one traced q3 and one traced q14: 72,000 bytes against the 5 ms of
    # the two takes inside the window
    want = 100.0 * 72_000 / 819e9 / 0.005
    assert reader.read(obs) == pytest.approx(want)
    assert 0 < reader.read(obs) < 100
    # no device plane (a rehearsal), or a trace without the program
    assert reader.read({**obs, "planes": None}) is None
    assert reader.read({**obs, "planes": one_chip(take_ms=())[:1]}) is None
    quiet = one_chip(take_ms=())
    quiet[1]["lines"][1]["events"] = quiet[1]["lines"][1]["events"][:1]
    assert reader.read({**obs, "planes": quiet}) is None
    with pytest.raises(KeyError):
        reader.read({**obs, "device": {"kind": "TPU v9"}})
