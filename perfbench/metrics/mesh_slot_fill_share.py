"""Share of the slots sent through the mesh exchange that held a live row:
the ``rows`` over the ``slots`` of the window's ``mesh.exchange`` events. A
send buffer of a whole input capacity a destination cannot pass 25% on four
chips; one sized from the counts is bounded by its bucket's slack."""

import _mesh

UNIT = "%"


after_query = _mesh.after_query


def read(obs):
    per_query = _mesh.exchanging(obs)
    if per_query is None:
        return None
    events = [e for events in per_query for e in events]
    slots = sum(int(e["slots"] or 0) for e in events)
    if slots <= 0:
        return None
    return 100.0 * sum(int(e["rows"] or 0) for e in events) / slots
