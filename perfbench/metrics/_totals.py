"""What the readers of the program's span totals share: the totals'
entries under a prefix at one moment, and what a window added to them.
``tracing.span_totals()`` keeps count and seconds by span name as each span
ends, and ``name:site`` beside ``name`` for a span that carries a site
(``launch:jit_join_ranges``, ``device.block:join.stats``), so a prefix that
ends in ``:`` takes a name's sites and leaves the name itself out. A
program without span totals, or without a key under the prefix, gives
nothing to read."""

from __future__ import annotations


def snapshot(prefix: str):
    """``{key: (count, seconds)}`` for the totals' keys under ``prefix``;
    None for a program that keeps no totals."""
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    if totals is None:
        return None
    return {key: (t["count"], t["seconds"])
            for key, t in totals().items() if key.startswith(prefix)}


def added(obs, reader: str, prefix: str = ""):
    """``(count, seconds)`` the window added over reader ``reader``'s
    snapshot keys that start with ``prefix``; None where the program has
    no such key."""
    before, after = obs["snapshots"][reader]
    if before is None or after is None:
        return None
    keys = [key for key in after if key.startswith(prefix)]
    if not keys:
        return None
    count = sum(after[k][0] - before.get(k, (0, 0.0))[0] for k in keys)
    seconds = sum(after[k][1] - before.get(k, (0, 0.0))[1] for k in keys)
    return count, seconds


def a_query(obs, reader: str, prefix: str, field: int):
    """The window's added count (``field`` 0) or seconds (1) over its
    queries."""
    got = added(obs, reader, prefix)
    queries = len(obs["window"]["queries"])
    if got is None or not queries:
        return None
    return got[field] / queries
