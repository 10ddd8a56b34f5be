"""BENCHMARK.json, the cell files and the readers agree, and every name and
unit is made of the characters the contract allows."""

import json
import os
import re

import pytest

import datagen
import reference
import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = run.read_json(run.ROOT, "BENCHMARK.json")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_units_use_allowed_characters():
    names = ([m["name"] for m in METRICS] + CELLS
             + [c["name"] for c in BENCH["configs"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [k for c in BENCH["configs"] for k in c["reduced"]])
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    for root, _, files in os.walk(run.HERE):
        if "__pycache__" in root:
            continue
        rel = os.path.relpath(root, run.ROOT)
        assert all(re.match(r"^[A-Za-z0-9_.\-/]+$", os.path.join(rel, f))
                   for f in files if not f.endswith(".pyc")), (rel, files)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_reader_exists_with_the_declared_unit(metric):
    reader = run.load_reader(metric["name"])
    assert reader.UNIT == metric["unit"]
    assert callable(reader.read)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_file_matches_benchmark_json(cell):
    reports = run.read_json(run.HERE, "cells", cell + ".json")
    for kind in ("end_to_end", "per_layer"):
        listed = {m["name"] for m in BENCH[kind]
                  if cell in m.get("workloads", CELLS)}
        assert set(reports[kind]) == listed, (cell, kind)
    assert "setup_s" in reports["end_to_end"]
    assert len(reports["end_to_end"]) >= 2 and reports["per_layer"]
    found = run.find_cell(cell)
    assert found["config"]["chips"] == found["chips"]
    for name, spec in found["queries"].items():
        assert set(spec["limits"]) == {"exact_mismatches", "sum_rel_gap",
                                       "quotient_abs_gap"}
        assert callable(reference.query(name))
        for table in spec["reads"]:  # each a file of its own
            columns = set(datagen.table(table).ARROW_SCHEMA.names)
            assert set(spec["reads"][table]) <= columns


def test_tables_that_make_rows_have_seed_ids_of_their_own():
    names = [f[:-3] for f in os.listdir(os.path.join(run.HERE, "tables"))
             if f.endswith(".py")]
    makers = [datagen.table(n) for n in names
              if not hasattr(datagen.table(n), "MADE_BY")]
    assert len({m.SEED_ID for m in makers}) == len(makers) > 0
    for n in names:
        made_by = getattr(datagen.table(n), "MADE_BY", n)
        assert callable(datagen.table(made_by).chunk)


def test_moves_names_an_end_to_end_metric_each_cell_reports():
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            reports = run.read_json(run.HERE, "cells", cell + ".json")
            assert m["moves"] in reports["end_to_end"], (m["name"], cell)


def test_config_files_hold_what_benchmark_json_says():
    for entry in BENCH["configs"]:
        config = run.read_json(run.ROOT, entry["file"])
        assert set(entry["reduced"]) == set(config["reduced"])
        assert config["assumed"] and config["guarantees"]
    assert len(json.dumps(BENCH)) < 64 * 1024
