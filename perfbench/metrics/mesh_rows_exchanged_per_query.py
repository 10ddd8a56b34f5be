"""Live rows sent through the mesh exchange a query: the ``rows`` of the
program's ``mesh.exchange`` events, a mean over the window's queries that
the deployment guarantees to exchange. For Q3 it is the rows of orders
and of lineitem that pass the query's filters, which ``mesh_bytes.py``
counts from the data."""

import _mesh
from _common import mean

UNIT = "rows"


after_query = _mesh.after_query


def read(obs):
    per_query = _mesh.exchanging(obs)
    if per_query is None:
        return None
    return mean(sum(int(e["rows"] or 0) for e in events)
                for events in per_query)
