"""Sides exchanged over the mesh a query: the program's ``mesh.exchange``
events (one a side of a join or an aggregate sent through ``all_to_all``),
a mean over the window's queries that the deployment guarantees to
exchange (``mesh_bytes.EXCHANGED``: Q3 here, two sides). Fewer launches
for the same rows are better, but 0 breaks the guarantee: if ANY such
query of the window exchanged nothing, the cell measured one chip there,
and this reads 0."""

import _mesh
from _common import mean

UNIT = "exchanges"


after_query = _mesh.after_query


def read(obs):
    per_query = _mesh.exchanging(obs)
    if per_query is None:
        return None
    counts = [len(events) for events in per_query]
    return 0.0 if min(counts) == 0 else mean(counts)
