"""Per query of the cell, the median of its latencies in the window (timed
by the client around ``collect()``); then the geometric mean over the cell's
queries."""

import math
import statistics

UNIT = "s"


def read(obs):
    by_query = {}
    for q in obs["window"]["queries"]:
        by_query.setdefault(q["query"], []).append(q["seconds"])
    if not by_query:
        return None
    medians = [statistics.median(v) for v in by_query.values()]
    return math.exp(sum(math.log(m) for m in medians) / len(medians))
