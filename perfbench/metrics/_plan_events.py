"""What the readers of a cached plan's events share: the program's
``scan.serve`` events (one a scan partition executed, with its ``table``,
live ``rows`` and ``how`` it was served) and ``repart.take`` events (one a
destination partition a repartition gathered, with its ``side`` and
``rows``), query by query from the program's ring. A program that has no
such event, as one older than the event is, gives nothing to read."""

from __future__ import annotations

EVENTS = {"scan.serve": ("table", "rows", "batches", "how"),
          "repart.take": ("side", "rows", "pieces", "capacity")}
READERS = ("scanned_rows_share", "repart_rows_per_query",
           "repart_take_roofline_share")
_last = (None, None)  # the query last asked about, and its events


def after_query(ctx, started, seconds) -> dict:
    """The ``after_query`` of every reader in ``READERS``: the events of
    ``EVENTS`` in the program's ring since the query ``started``, by name
    (one scan of the ring a query, whichever reader asks)."""
    global _last
    if _last[0] != started:
        from ballista_tpu.observability.tracing import ring_records

        kept = {name: [] for name in EVENTS}
        for r in ring_records(since=started):
            keys = EVENTS.get(r.get("name"))
            if keys is not None:
                kept[r["name"]].append({k: r.get(k) for k in keys})
        _last = (started, kept)
    return _last[1]


def known(event: str) -> bool:
    """Whether the program has emitted ``event`` at all."""
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    return totals is not None and event in totals()


def per_query(obs, event: str):
    """``[(query name, its events of that name)]`` over the window's
    recorded queries, from whichever of ``READERS`` the cell lists; None
    where the program has no such event or no query was recorded."""
    if not known(event):
        return None
    got = []
    for q in obs["window"]["queries"]:
        kept = ((q.get("record") or {}).get("readers") or {})
        events = next((kept[r] for r in READERS if r in kept), None)
        if events is not None:
            got.append((q["query"], events[event]))
    return got or None
