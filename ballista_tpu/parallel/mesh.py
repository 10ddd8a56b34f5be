"""Mesh query runner: whole query stages as one SPMD XLA program.

Shards table partitions over the devices of a ``jax.sharding.Mesh`` and
runs scan-pipeline + two-phase aggregation with XLA collectives:

- per-device pipelines (filter/project/partial-agg) trace exactly like the
  single-chip operators;
- hash repartition = ICI ``all_to_all`` (kernels.mesh_shuffle);
- aggregate merge = ``all_gather`` of the partial group tables, final
  aggregation replicated (cheap: group tables are small).

This is the slice-internal fast path the SURVEY maps the reference's
Flight shuffle onto (SURVEY §5.7/§5.8); across hosts/slices the
distributed runtime's data plane takes over.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar import Column, ColumnBatch
from ..datatypes import Schema
from ..errors import ExecutionError
from ..kernels import mesh_shuffle

DATA_AXIS = "data"


def make_mesh(n_devices: Optional[int] = None, axis: str = DATA_AXIS) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ExecutionError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis,))


def _stack_batches(schema: Schema, batches: List[ColumnBatch]):
    """[per-device ColumnBatch] -> stacked leaves [n_dev, cap] on host."""
    caps = {b.capacity for b in batches}
    if len(caps) != 1:
        raise ExecutionError(f"device batches must share capacity, got {caps}")
    from ..observability.tracing import trace_span

    cols = {}
    # the relayout round-trips every column through host memory — a
    # real blocking sync the profiler must attribute to device time
    with trace_span("device.block", site="mesh.stack",
                    n=len(batches)):
        for i, f in enumerate(schema.fields):
            cols[f.name] = np.stack(
                [np.asarray(b.columns[i].values) for b in batches]
            )
        sel = np.stack([np.asarray(b.selection) for b in batches])
    dicts = {
        f.name: batches[0].columns[i].dictionary
        for i, f in enumerate(schema.fields)
    }
    return cols, sel, dicts


class MeshQueryRunner:
    """Runs a per-device batch transform + merge under shard_map."""

    def __init__(self, mesh: Mesh, axis: str = DATA_AXIS):
        self.mesh = mesh
        self.axis = axis
        self.n_dev = mesh.devices.size

    def run_spmd(
        self,
        schema: Schema,
        batches: List[ColumnBatch],  # one per device
        device_fn: Callable,  # (cols dict, live) -> pytree of [*] arrays
        replicated_out: bool = True,
    ):
        """Shard the stacked batches over the mesh and run device_fn
        SPMD. device_fn may use lax collectives over the data axis."""
        cols, sel, dicts = _stack_batches(schema, batches)
        sharding = NamedSharding(self.mesh, P(self.axis))

        cols_dev = {
            k: jax.device_put(v, sharding) for k, v in cols.items()
        }
        sel_dev = jax.device_put(sel, sharding)

        out_spec = P() if replicated_out else P(self.axis)

        @partial(
            shard_map,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis)),
            out_specs=out_spec,
            check_vma=False,
        )
        def run(cols_blk, sel_blk):
            # shard_map gives [1, cap] blocks; squeeze the device axis
            cols1 = {k: v[0] for k, v in cols_blk.items()}
            live1 = sel_blk[0]
            out = device_fn(cols1, live1)
            if replicated_out:
                return out
            return jax.tree_util.tree_map(lambda x: x[None], out)

        # deliberately NOT governed: device_fn is an arbitrary caller
        # closure, so the only sound cache key is its identity — callers
        # pass fresh lambdas, giving a 0% hit rate while the cache would
        # pin the closures (and whatever they capture) process-wide. A
        # transient jit matches the utility-API lifetime.
        return jax.jit(run)(cols_dev, sel_dev), dicts  # jit-ok: transient

    # convenience: hash-repartition rows across the mesh ---------------------

    def shuffle_fn(self, key_col: str, dest_capacity: int):
        """Returns a traced helper usable inside device_fn:
        (cols, live) -> (cols', live', overflowed). ``overflowed`` is a
        traced bool — True when some device had more than dest_capacity
        rows for one destination, in which case rows were DROPPED and the
        caller must re-run with a larger capacity (check it host-side)."""
        axis = self.axis
        n_dev = self.n_dev

        def do_shuffle(cols: Dict[str, jax.Array], live: jax.Array):
            names = list(cols.keys())
            dest = mesh_shuffle.destination_ids(cols[key_col], live, n_dev)
            out_cols, out_live, counts = mesh_shuffle.all_to_all_rows(
                [cols[n] for n in names], live, dest, axis, n_dev,
                dest_capacity,
            )
            over = jnp.max(counts) > dest_capacity
            # any device overflowing poisons the global result
            overflowed = lax.pmax(over, axis)
            return dict(zip(names, out_cols)), out_live, overflowed

        return do_shuffle
