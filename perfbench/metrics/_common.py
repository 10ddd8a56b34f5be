"""Small helpers the readers share. A reader is ``metrics/<name>.py`` with a
``UNIT`` and ``read(obs)``, which returns a number, or None where it finds
nothing to read (the harness then leaves the metric out of the line). It may
also have ``snapshot()`` and ``after_query(ctx, started, seconds)``: see
README.md."""

from __future__ import annotations


def records(obs) -> list:
    """The per-query records of the window (a traced run keeps them)."""
    return [q["record"] for q in obs["window"]["queries"]
            if q.get("record") is not None]


def own(obs, name: str) -> list:
    """What reader ``name``'s ``after_query`` returned, query by query."""
    return [r["readers"][name] for r in records(obs)
            if name in r.get("readers", {})]


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None


def phase_mean(obs, *phases):
    """Mean over the window's queries of the summed ledger phases."""
    return mean(sum(float(r["phases"].get(p, 0.0)) for p in phases)
                for r in records(obs) if r["phases"])


def percentile(values, share: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``share``
    of the sample at or below it."""
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * share // 1))
    return ordered[int(rank) - 1]
