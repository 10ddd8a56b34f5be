"""Seconds of ``all_to_all`` collectives a query: the union of such
operations on each chip's operation lines in the traced rounds, a mean
over the cell's chips, over the traced queries that the deployment
guarantees to exchange (``mesh_bytes.EXCHANGED``)."""

import _mesh

UNIT = "s"


def read(obs):
    got = _mesh.exchange_seconds(obs)
    if got is None:
        return None
    total, queries, _, _ = got
    return total / queries
