"""Blocking device-to-host reads a query's shuffle writes made: the count of
the program's ``device.block`` spans at the site ``ipc.batch_to_arrow`` (one
for a slice's mask and one for each of its columns), from its per-name span
totals, which no ring bounds: the window's delta over its queries. A program
whose totals have no such key gives nothing to read."""

import _totals

UNIT = "reads"
SPAN = "device.block:ipc.batch_to_arrow"


def snapshot():
    return _totals.snapshot(SPAN)


def read(obs):
    return _totals.a_query(obs, "shuffle_d2h_reads_per_query", SPAN, 0)
