"""Rows a query's repartitions gathered into their destination partitions:
the ``rows`` of the program's ``repart.take`` events, a mean over the
window's queries. A cached plan keeps a repartition's sorted sources, so a
warm query pays the takes only, and only of the repartitions that no kept
join build stands above. A program without the event gives nothing to
read."""

import _plan_events
from _common import mean

UNIT = "rows"

after_query = _plan_events.after_query


def read(obs):
    per_query = _plan_events.per_query(obs, "repart.take")
    if per_query is None:
        return None
    return mean(sum(int(e["rows"] or 0) for e in events)
                for _, events in per_query)
