"""Seconds a served query's tasks cost the scheduler to hand out: the seconds
of the program's ``scheduler.task_dispatch`` spans (inside ``PollWork``: the
stage's plan resolved against its inputs' locations and serialised for the
executor), the window's delta over its queries. A standalone program never
opens the span, and gives nothing to read."""

import _totals

UNIT = "s"
SPAN = "scheduler.task_dispatch"


def snapshot():
    return _totals.snapshot(SPAN)


def read(obs):
    return _totals.a_query(obs, "task_dispatch_s_per_query", SPAN, 1)
