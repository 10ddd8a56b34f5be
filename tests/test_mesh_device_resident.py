"""Device-resident mesh input path (SURVEY §7 "device-memory partition
cache").

Round 2's mesh path executed fused-stage producers on host, concatenated
every column in numpy, and re-uploaded per fused stage. These tests pin
the round-3 replacement: producer output is laid out over the mesh with
device gathers only (scalar live-count syncs are the only host reads),
and a fused stage whose producer is itself mesh-fused consumes the
producer's stacked HBM output directly — no re-assembly, no host
round-trip, and still zero shuffle files.
"""

import os

import numpy as np
import pandas as pd
import pytest

import jax

from ballista_tpu import Decimal, Int64, Utf8, schema
from ballista_tpu.client import BallistaContext
from ballista_tpu.distributed.executor import LocalCluster
from ballista_tpu.io import TblSource
from ballista_tpu.physical import mesh_input


def _no_shuffle_files(cluster):
    files = []
    for e in cluster.executors:
        for root, _, fs in os.walk(e.config.work_dir):
            files += [f for f in fs if f.startswith("shuffle-")]
    return files == [], files


def test_assemble_over_mesh_unifies_dictionaries(eight_devices, tmp_path):
    """Producer partitions with DIFFERENT utf8 dictionaries are unified
    on device: the stacked batch shares one dictionary and decodes to
    exactly the host rows."""
    from ballista_tpu.io import MemTableSource
    from ballista_tpu.logical import LogicalPlanBuilder
    from ballista_tpu.parallel.mesh import make_mesh
    from ballista_tpu.physical.planner import (
        PlannerOptions, create_physical_plan,
    )

    from ballista_tpu.columnar import ColumnBatch

    s = schema(("k", Utf8), ("v", Int64))
    # two partitions built independently -> distinct dictionaries
    parts = [
        {"k": ["apple", "pear", "apple"], "v": [1, 2, 3]},
        {"k": ["kiwi", "pear", "zucchini", "kiwi"], "v": [4, 5, 6, 7]},
    ]
    src = MemTableSource(
        s, [[ColumnBatch.from_pydict(s, p)] for p in parts]
    )
    plan = LogicalPlanBuilder.scan("t", src).build()
    phys = create_physical_plan(plan, PlannerOptions())

    mesh = make_mesh(8)
    mesh_input.reset_stats()
    stacked, cap = mesh_input.stacked_input(phys, s, mesh)
    assert mesh_input.STATS["slot_assemblies"] == 1

    # one shared dictionary across every device slot
    kcol = stacked.columns[0]
    assert kcol.dictionary is not None
    got = []
    for q in range(8):
        codes = np.asarray(kcol.values[q])
        live = np.asarray(stacked.selection[q])
        got += [kcol.dictionary.values[c] for c in codes[live]]
    exp = [k for p in parts for k in p["k"]]
    assert sorted(got) == sorted(exp)

    vcol = stacked.columns[1]
    got_v = []
    for q in range(8):
        live = np.asarray(stacked.selection[q])
        got_v += list(np.asarray(vcol.values[q])[live])
    assert sorted(got_v) == list(range(1, 8))


def test_chained_fused_stages_stay_in_hbm(eight_devices, tmp_path):
    """q5 shape: partitioned join AND shuffled aggregation both fuse; the
    aggregation's producer contains the fused join, so its input must be
    the join's stacked HBM output (chained), never a host re-assembly —
    and the whole query writes zero shuffle files."""
    d = tmp_path / "dim"
    d.mkdir()
    (d / "p0.tbl").write_text(
        "".join(f"{i}|cat{i % 5}|\n" for i in range(17)))
    f = tmp_path / "fact"
    f.mkdir()
    for part in range(3):
        rows = [f"{i}|{i % 17}|{i + 0.25:.2f}|\n"
                for i in range(300) if i % 3 == part]
        (f / f"p{part}.tbl").write_text("".join(rows))

    dim_s = schema(("dkey", Int64), ("cat", Utf8))
    fact_s = schema(("fid", Int64), ("fkey", Int64), ("v", Decimal(2)))
    cluster = LocalCluster(num_executors=1, concurrent_tasks=2,
                           num_devices=8)
    try:
        mesh_input.reset_stats()
        ctx = BallistaContext.remote(
            "localhost", cluster.port,
            **{"join.partitioned.threshold": "1", "join.partitions": "8",
               "agg.partitions": "8", "mesh.devices": "8"},
        )
        ctx.register_source("dim", TblSource(str(d), dim_s),
                            primary_key="dkey")
        ctx.register_source("fact", TblSource(str(f), fact_s))
        got = ctx.sql(
            "select cat, sum(v) as sv, count(*) as n from fact, dim "
            "where fkey = dkey group by cat order by cat"
        ).collect()

        a = np.arange(300)
        fd = pd.DataFrame({"fkey": a % 17, "v": a + 0.25})
        fd["cat"] = fd.fkey.map(lambda k: f"cat{k % 5}")
        exp = fd.groupby("cat").agg(sv=("v", "sum"), n=("v", "size")) \
            .reset_index().sort_values("cat")
        np.testing.assert_array_equal(got["cat"], exp["cat"])
        np.testing.assert_allclose(got["sv"], exp["sv"], rtol=1e-9)
        np.testing.assert_array_equal(got["n"].astype(np.int64),
                                      exp["n"].astype(np.int64))

        # the fused agg consumed the fused join's stacked output in HBM
        assert mesh_input.STATS["chained_stages"] >= 1, mesh_input.STATS
        ok, files = _no_shuffle_files(cluster)
        assert ok, f"host shuffle files written: {files}"
    finally:
        cluster.shutdown()


def test_host_funnel_is_gone():
    """The round-2 numpy producer funnel must not exist: mesh execs have
    no code path that materializes producer columns with np.asarray."""
    from ballista_tpu.physical import mesh_agg

    assert not hasattr(mesh_agg, "_run_producer_over_mesh")
    assert not hasattr(mesh_agg, "_stack_device_batches")


def test_stacked_compaction_bounds_chain_capacity(eight_devices):
    """A sparse stacked batch (few live rows in a huge capacity) is
    compacted per device before feeding the next fused stage, bounding
    the all_to_all buffer blowup in fused chains."""
    from ballista_tpu.columnar import ColumnBatch
    from ballista_tpu.parallel.mesh import make_mesh

    s = schema(("v", Int64))
    mesh = make_mesh(8)
    slot_batches = []
    for q in range(8):
        b = ColumnBatch.from_numpy(
            s, {"v": np.arange(3, dtype=np.int64) + 10 * q}, capacity=8192
        )
        slot_batches.append(b)
    stacked = mesh_input.stack_to_mesh(slot_batches, mesh)
    out = mesh_input._maybe_compact_stacked(stacked, mesh)
    # 8192 -> 1024: the bucket ladder's floor, the rule maybe_compact
    # follows too (whether an ICI slot should go lower is ROADMAP R3)
    assert int(out.selection.shape[1]) == 1024
    for q in range(8):
        live = np.asarray(out.selection[q])
        assert list(np.asarray(out.columns[0].values[q])[live]) == \
            [10 * q, 10 * q + 1, 10 * q + 2]
