"""Programs launched a query: the count of the program's ``launch:<program>``
spans (one a call through ``compile/governor.py`` ``call_with``, the one way
to a compiled program), the window's delta over its queries. Calls that
compiled are ``launch.cold:*`` and not counted, so this is warm dispatch
only. A program without launch spans gives nothing to read."""

import _totals

UNIT = "launches"
PREFIX = "launch:"


def snapshot():
    return _totals.snapshot(PREFIX)


def read(obs):
    return _totals.a_query(obs, "launches_per_query", PREFIX, 0)
