"""What the readers of the layer "mesh exchange" share: the program's
``mesh.exchange`` events query by query (one a side exchanged over the
mesh, with the live ``rows``, the ``slots`` sent and the ``bytes``), and
the all_to_all collectives of a device trace chip by chip. A program that
has no such event, as one older than the event is, gives nothing to read."""

from __future__ import annotations

import re


EVENT = "mesh.exchange"
COLLECTIVE = re.compile(r"all-to-all", re.IGNORECASE)


READERS = ("mesh_exchanges_per_query", "mesh_rows_exchanged_per_query",
           "mesh_slot_fill_share")
_last = (None, None)  # the query last asked about, and its events


def after_query(ctx, started, seconds) -> list:
    """The ``after_query`` of every reader in ``READERS``: the
    ``mesh.exchange`` records of the program's ring since the query
    ``started`` (one scan of the ring a query, whichever reader asks)."""
    global _last
    if _last[0] != started:
        from ballista_tpu.observability.tracing import ring_records

        _last = (started, [
            {k: r.get(k) for k in ("side", "rows", "slots", "bytes")}
            for r in ring_records(since=started) if r.get("name") == EVENT])
    return _last[1]


def known() -> bool:
    """Whether the program has emitted the event at all."""
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    return totals is not None and EVENT in totals()


def exchanging(obs) -> list:
    """Per query of the window that the deployment guarantees to exchange
    (``mesh_bytes.EXCHANGED``): the events ``after_query`` kept for it,
    under whichever of ``READERS`` the cell lists. None where the program
    has no such event or no query was recorded."""
    import mesh_bytes

    if not known():
        return None
    got = []
    for q in obs["window"]["queries"]:
        kept = ((q.get("record") or {}).get("readers") or {})
        events = next((kept[r] for r in READERS if r in kept), None)
        if events is not None and mesh_bytes.exchanges(q["query"]):
            got.append(events)
    return got or None


def chip_lines(obs):
    """``(chips, window, notes)``: per chip of the cell, in plane order,
    the ``[name, start_ns, dur_ns]`` events of its operation lines; the
    traced queries' ``(start, end)``; and their annotations. None without
    a device plane (a CPU rehearsal) or without a traced query."""
    import xplane

    planes = obs.get("planes")
    if not planes:
        return None
    devices = [p for p in planes if xplane.DEVICE_PLANE.match(p["name"])]
    notes = [ev for p in planes if not p["name"].startswith("/device:")
             for ln in p["lines"] for ev in ln["events"]
             if ev[0].startswith(xplane.ANNOTATION)]
    if not devices or not notes:
        return None
    window = (min(s for _, s, _ in notes), max(s + d for _, s, d in notes))
    chips = []
    for p in devices[:obs["cell"]["chips"]]:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        chips.append([ev for name in xplane.OPS_LINES
                      for ev in lines.get(name) or []])
    return chips, window, notes


def clipped(events, window) -> list:
    """Merged ``[start, end)`` intervals of ``events`` inside ``window``."""
    import xplane

    w0, w1 = window
    return xplane.union([max(s, w0), min(s + d, w1)] for _, s, d in events
                        if min(s + d, w1) > max(s, w0))


def seconds(intervals) -> float:
    return sum(e - s for s, e in intervals) / 1e9


def traced_exchanging(notes) -> int:
    """How many of the traced queries exchange by the deployment's
    guarantee."""
    import mesh_bytes
    import xplane

    return sum(1 for name, _, _ in notes
               if mesh_bytes.exchanges(name[len(xplane.ANNOTATION):]))


def exchange_seconds(obs):
    """``(seconds of all_to_all collectives, a mean over the chips;
    traced queries that exchange; per chip (collective, other)
    intervals; the traced queries' annotations)``, or None where there is
    nothing to read."""
    got = chip_lines(obs)
    if got is None:
        return None
    chips, window, notes = got
    per_chip = []
    for ops in chips:
        coll = clipped([ev for ev in ops if COLLECTIVE.search(ev[0])], window)
        rest = clipped([ev for ev in ops if not COLLECTIVE.search(ev[0])],
                       window)
        per_chip.append((coll, rest))
    n = traced_exchanging(notes)
    total = sum(seconds(c) for c, _ in per_chip) / len(per_chip)
    if not n or total <= 0:
        return None
    return total, n, per_chip, notes


