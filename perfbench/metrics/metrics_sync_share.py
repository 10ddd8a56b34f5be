"""Share of the seconds a window's host threads spent blocked on the device
that the instrumentation's own reads cost: the seconds of ``device.block``
spans at the site ``metrics.rows`` (``observability/metrics.py``: a row count
read for an operator's metrics) over those of all ``device.block`` spans.
A program whose totals have no ``device.block:<site>`` keys gives nothing to
read."""

import _totals

UNIT = "%"
SPAN = "device.block"
SITE = SPAN + ":metrics.rows"


def snapshot():
    return _totals.snapshot(SPAN)


def read(obs):
    sites = _totals.added(obs, "metrics_sync_share", SPAN + ":")
    if sites is None or sites[1] <= 0:
        return None
    own = _totals.added(obs, "metrics_sync_share", SITE)
    return 100.0 * (own[1] if own else 0.0) / sites[1]
