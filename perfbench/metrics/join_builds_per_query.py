"""Join build sides made a query: the count of the program's ``join.build``
spans (one a build side materialised: its subtree run, concatenated and
sorted or tabled), the window's delta over its queries. 0 when every
execution of the window found its builds in the cached plan
(``join.build_reused``); the first execution of a plan, in warm-up, pays
them. A program without the span gives nothing to read."""

UNIT = "builds"
SPAN = "join.build"


def snapshot():
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    if totals is None or SPAN not in totals():
        return None
    return int(totals()[SPAN]["count"])


def read(obs):
    before, after = obs["snapshots"]["join_builds_per_query"]
    queries = len(obs["window"]["queries"])
    if before is None or after is None or not queries:
        return None
    return (after - before) / queries
