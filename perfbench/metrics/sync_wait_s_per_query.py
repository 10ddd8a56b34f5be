"""Seconds a query's host threads spent blocked on the device: the seconds of
the program's ``device.block`` spans, from its per-name span totals (kept as
each span ends, so no flight-recorder ring bounds them), the window's delta
over its queries. A sum over the executors' task threads in a served cell, so
it can pass the query's wall time there. In a traced run the record the
harness takes after a standalone query costs a sync of its own (a fetch from
an idle device), which is in the delta. A program without span totals gives
nothing to read."""

UNIT = "s"
SPAN = "device.block"


def snapshot():
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    if totals is None:
        return None
    return float(totals().get(SPAN, {}).get("seconds", 0.0))


def read(obs):
    before, after = obs["snapshots"]["sync_wait_s_per_query"]
    queries = len(obs["window"]["queries"])
    if before is None or after is None or not queries:
        return None
    return (after - before) / queries
