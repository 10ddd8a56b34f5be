"""Blocking host<-device syncs a query: the ``device.block`` spans in the
program's flight recorder since the query started (a bounded ring: a query
that emits more records than it holds is under-counted)."""

from _common import mean, own

UNIT = "syncs"


def after_query(ctx, started, seconds):
    from ballista_tpu.observability.tracing import ring_records

    return sum(1 for r in ring_records(since=started)
               if r.get("name") == "device.block")


def read(obs):
    return mean(own(obs, "syncs_per_query"))
