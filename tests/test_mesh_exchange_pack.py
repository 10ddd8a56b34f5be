"""The mesh exchange's pack (``kernels/mesh_shuffle.all_to_all_rows``)
against a plain reference: row r goes to ``hash_partition_ids(key) mod
n_dev``, and the rows a device receives from one source keep the order
they had there. Four of conftest's eight CPU devices."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ballista_tpu.compile import bucket_capacity
from ballista_tpu.kernels import mesh_shuffle
from ballista_tpu.kernels.hashing import hash_partition_ids

N_DEV = 4


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.asarray(jax.devices()[:N_DEV]), ("data",))


def exchange(mesh, columns, live, slots):
    """``all_to_all_rows`` over [n_dev, n] inputs hashed on column 0."""
    specs = (P("data"),) * (len(columns) + 1)

    @functools.partial(shard_map, mesh=mesh, in_specs=specs,
                       out_specs=(P("data"), P("data"), P("data")),
                       check_vma=False)
    def run(live_, *cols):
        cols = [c[0] for c in cols]
        dest = mesh_shuffle.destination_ids(cols[0], live_[0], N_DEV)
        out, out_live, counts = mesh_shuffle.all_to_all_rows(
            cols, live_[0], dest, "data", N_DEV, dest_capacity=slots)
        return [c[None] for c in out], out_live[None], counts[None]

    out, out_live, counts = jax.jit(run)(jnp.asarray(live),
                                         *map(jnp.asarray, columns))
    return [np.asarray(c) for c in out], np.asarray(out_live), \
        np.asarray(counts)


def reference(columns, live, slots):
    """Per destination: for each source in turn its first ``slots`` live
    rows bound there, in their order; and the [source, destination] counts."""
    dest = np.asarray(hash_partition_ids(
        jnp.asarray(columns[0]).astype(jnp.int64).reshape(-1), N_DEV)
    ).reshape(columns[0].shape)
    got = {d: [[] for _ in columns] for d in range(N_DEV)}
    counts = np.zeros((N_DEV, N_DEV), np.int64)
    for src in range(N_DEV):
        for d in range(N_DEV):
            rows = np.flatnonzero(live[src] & (dest[src] == d))
            counts[src, d] = len(rows)
            for i, c in enumerate(columns):
                got[d][i].append(c[src][rows[:slots]])
    return {d: [np.concatenate(c) for c in cols]
            for d, cols in got.items()}, counts


def received(out, out_live, d):
    return [c[d][out_live[d]] for c in out]


def keys_for(kind, rng, n, dtype):
    if kind == "hot":
        k = np.where(rng.random((N_DEV, n)) < 0.9, 7,
                     rng.integers(0, 1 << 30, (N_DEV, n)))
    elif kind == "one_destination":
        pool = np.arange(4096)
        dest = np.asarray(hash_partition_ids(
            jnp.asarray(pool, jnp.int64), N_DEV))
        k = rng.choice(pool[dest == 2], (N_DEV, n))
    else:
        k = rng.integers(-(1 << 30), 1 << 30, (N_DEV, n))
    return k.astype(dtype)


CASES = {
    # name: (keys, rows a device, live share, key dtype)
    "uniform": ("uniform", 4096, 0.7, np.int64),
    "one_hot_key": ("hot", 4096, 0.8, np.int64),
    "all_rows_to_one_destination": ("one_destination", 2048, 1.0, np.int64),
    "no_live_row": ("uniform", 1024, 0.0, np.int64),
    "capacity_off_a_bucket_edge": ("uniform", 5000, 0.5, np.int64),
    "int32_keys": ("uniform", 3000, 0.6, np.int32),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pack_equals_the_plain_reference(mesh, case):
    kind, n, share, dtype = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    keys = keys_for(kind, rng, n, dtype)
    live = rng.random((N_DEV, n)) < share
    # payload: an int64, an int32, and three validity planes (two of them
    # share the exchanged word with the third)
    columns = [keys, rng.integers(-(1 << 60), 1 << 60, (N_DEV, n)),
               rng.integers(0, 1 << 20, (N_DEV, n)).astype(np.int32),
               rng.random((N_DEV, n)) < 0.5, rng.random((N_DEV, n)) < 0.9,
               np.ones((N_DEV, n), bool)]
    _, counts = reference(columns, live, n)
    # slots as the operators size them: the bucket of the largest count
    slots = bucket_capacity(max(int(counts.max()), 1))
    want, _ = reference(columns, live, slots)
    out, out_live, sent = exchange(mesh, columns, live, slots)
    assert (sent == counts).all()
    assert out_live.shape == (N_DEV, N_DEV * slots)
    for d in range(N_DEV):
        for got, exp in zip(received(out, out_live, d), want[d]):
            assert got.dtype == exp.dtype
            np.testing.assert_array_equal(got, exp)


def test_too_few_slots_are_reported_and_the_retry_is_whole(mesh):
    """One hot key with slots sized for a uniform hash: the counts say the
    exchange overflowed (callers run again with their bucket), what was
    delivered is each source's FIRST rows, and the retry delivers all."""
    rng = np.random.default_rng(99)
    n = 2048
    keys = keys_for("hot", rng, n, np.int64)
    live = np.ones((N_DEV, n), bool)
    columns = [keys, np.arange(N_DEV * n).reshape(N_DEV, n)]
    tight = bucket_capacity(n // N_DEV)
    out, out_live, sent = exchange(mesh, columns, live, tight)
    assert sent.max() > tight  # the overflow the caller checks for
    want, counts = reference(columns, live, tight)
    for d in range(N_DEV):
        np.testing.assert_array_equal(received(out, out_live, d)[1],
                                      want[d][1])
    retry = bucket_capacity(int(sent.max()))
    out, out_live, sent = exchange(mesh, columns, live, retry)
    assert sent.max() <= retry and out_live.sum() == live.sum()
    want, _ = reference(columns, live, retry)
    for d in range(N_DEV):
        np.testing.assert_array_equal(received(out, out_live, d)[1],
                                      want[d][1])


def test_destination_counts_are_the_references(mesh):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 1 << 40, 3000)
    live = rng.random(3000) < 0.4
    dest = mesh_shuffle.destination_ids(jnp.asarray(keys), jnp.asarray(live),
                                        N_DEV)
    got = np.asarray(mesh_shuffle.destination_counts(jnp.asarray(live), dest,
                                                     N_DEV))
    d = np.asarray(dest)
    assert got.tolist() == [int((live & (d == k)).sum())
                            for k in range(N_DEV)]
