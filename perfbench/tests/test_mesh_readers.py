"""The readers of the layer "mesh exchange": the cell and its files are
found by name, the counter readers on hand-made records, the device-trace
readers on a synthetic trace of four chip planes, ``mesh_bytes`` against
the generated data, and that a program without the ``mesh.exchange`` event,
or a run without a device plane, gives every one of them nothing to read."""

import json
import os

import pytest

import run

MS = 1_000_000  # nanoseconds


def test_the_cell_and_its_files_are_found_by_name():
    cell = run.find_cell("mesh4-join")
    assert cell["chips"] == 4 and cell["config"]["devices"] == 4
    assert cell["config"]["executors"] == 1
    assert cell["traffic"]["round"] == ["q3", "q14"]
    bench = run.read_json(run.ROOT, "BENCHMARK.json")
    listed = {m["name"] for m in bench["per_layer"]
              if m.get("workloads") == ["mesh4-join"]}
    assert listed and listed <= set(cell["reports"]["per_layer"])
    for name in cell["reports"]["per_layer"] + cell["reports"]["end_to_end"]:
        assert hasattr(run.load_reader(name), "read")
    served = run.read_json(run.HERE, "configs", "tpch-sf3-served.json")
    assert cell["config"]["guarantees"][:2] == served["guarantees"]
    assert cell["config"]["environment"] == served["environment"]


def window(per_query, reader):
    return {"window": {"queries": [
        {"query": q, "started": 0.0, "seconds": 1.0,
         "record": {"phases": {}, "readers": {reader: events}}}
        for q, events in per_query]}}


def side(rows, slots):
    return {"side": "s", "rows": rows, "slots": slots, "bytes": rows * 28}


def shared_helper():
    """``metrics/_mesh.py`` as the readers import it."""
    run.load_reader("mesh_slot_fill_share")  # puts metrics/ on the path
    import _mesh

    return _mesh


@pytest.fixture
def event_known(monkeypatch):
    monkeypatch.setattr(shared_helper(), "known", lambda: True)


def test_exchanges_rows_and_fill_a_query(event_known):
    q3 = [side(900, 1024), side(100, 1024)]
    for name, want in (("mesh_exchanges_per_query", 2.0),
                       ("mesh_rows_exchanged_per_query", 1000.0),
                       ("mesh_slot_fill_share", 100.0 * 1000 / 2048)):
        reader = run.load_reader(name)
        obs = window([("q3", q3), ("q14", []), ("q3", q3)], name)
        assert reader.read(obs) == pytest.approx(want)
        assert reader.after_query(None, 0.0, 1.0) == []  # no event here
    # one q3 of the window exchanged nothing: the guarantee is broken
    reader = run.load_reader("mesh_exchanges_per_query")
    broken = window([("q3", q3), ("q3", [])], "mesh_exchanges_per_query")
    assert reader.read(broken) == 0.0


@pytest.mark.parametrize("name", ["mesh_exchanges_per_query",
                                  "mesh_rows_exchanged_per_query",
                                  "mesh_slot_fill_share"])
def test_a_program_without_the_event_gives_nothing(name, monkeypatch):
    monkeypatch.setattr(shared_helper(), "known", lambda: False)
    reader = run.load_reader(name)
    assert reader.read(window([("q3", []), ("q14", [])], name)) is None


def four_planes(busy_ms=(40, 10, 10, 10)):
    """One traced q3 and one q14, 100 ms each. Every chip runs a 6 ms
    all_to_all inside q3, of which 2 ms lie under another operation; chip
    k is busy ``busy_ms[k]`` in all."""
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ["collect:q3", 0, 100 * MS], ["collect:q14", 100 * MS, 100 * MS]]}]}
    planes = [host]
    for k, busy in enumerate(busy_ms):
        ops = [["all-to-all.7", 10 * MS, 6 * MS],
               ["fusion.3", 14 * MS, 2 * MS],
               ["gather.9", 30 * MS, (busy - 6) * MS]]
        planes.append({"name": f"/device:TPU:{k}", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules",
             "events": [["jit_mesh_join_spmd(1)", 10 * MS, busy * MS]]}]})
    return planes


def traced_obs(planes, data_dir=None):
    cell = run.find_cell("mesh4-join")
    return {"cell": cell, "planes": planes, "data_dir": data_dir,
            "device": {"kind": "TPU v5 lite"}}


def test_exchange_seconds_exposed_share_and_busy_spread():
    obs = traced_obs(four_planes())
    assert run.load_reader("mesh_exchange_s_per_query").read(obs) == \
        pytest.approx(0.006)  # one traced q3; q14 does not exchange
    assert run.load_reader("mesh_exchange_exposed_share").read(obs) == \
        pytest.approx(100.0 * 4 / 6)
    # busy 40, 10, 10, 10 ms: (40 - 10) over their mean of 17.5
    assert run.load_reader("chip_busy_spread").read(obs) == \
        pytest.approx(100.0 * 30 / 17.5)


@pytest.mark.parametrize("name", ["mesh_exchange_s_per_query",
                                  "mesh_exchange_exposed_share",
                                  "ici_roofline_share", "chip_busy_spread"])
def test_no_device_plane_or_no_collective_gives_nothing(name):
    reader = run.load_reader(name)
    assert reader.read(traced_obs(None)) is None  # a rehearsal
    assert reader.read(traced_obs(four_planes()[:1])) is None
    if name != "chip_busy_spread":
        quiet = four_planes()
        for p in quiet[1:]:
            p["lines"][0]["events"] = p["lines"][0]["events"][1:]
        assert reader.read(traced_obs(quiet)) is None


def test_mesh_bytes_and_the_roofline_share(tmp_path):
    import datagen
    import mesh_bytes
    from reference import D, load

    data_dir = str(tmp_path)
    datagen.generate(data_dir, 0.01, ["customer", "orders", "lineitem"], 4, 7)
    got = {s["side"]: s for s in mesh_bytes.sides("q3", data_dir)}
    l = load(data_dir, "lineitem", ["l_shipdate"])
    assert got["lineitem"]["rows"] == int(
        (l["l_shipdate"] > D("1995-03-15")).sum())
    assert got["lineitem"]["row_bytes"] == 24  # key, price, discount
    assert got["orders"]["row_bytes"] == 16  # key, date (int32), priority
    assert 0 < got["orders"]["rows"] < 15_000 * 0.25
    assert mesh_bytes.sides("q14", data_dir) == []
    assert not mesh_bytes.exchanges("q14") and mesh_bytes.exchanges("q3")
    crossing = mesh_bytes.crossing_bytes("q3", data_dir, 4)
    assert crossing == pytest.approx(0.75 * sum(
        s["rows"] * s["row_bytes"] for s in got.values()))
    # one traced q3: each chip sends a quarter of the crossing bytes at
    # the peak, against 6 ms of collectives
    peak = json.load(open(os.path.join(run.HERE, "peaks_ici.json")))[
        "TPU v5 lite"]["ici_bytes_per_s"]
    share = run.load_reader("ici_roofline_share").read(
        traced_obs(four_planes(), data_dir))
    assert share == pytest.approx(100.0 * crossing / 4 / peak / 0.006)
    assert 0 < share < 100
