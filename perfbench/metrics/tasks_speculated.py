"""Straggler tasks the scheduler duplicated onto another executor in the
window: the count of the program's ``scheduler.speculate`` events, from its
per-name span totals. On one chip a duplicate shares the device with the task
it copies. A program without span totals gives nothing to read."""

UNIT = "tasks"
EVENT = "scheduler.speculate"


def snapshot():
    from ballista_tpu.observability import tracing

    totals = getattr(tracing, "span_totals", None)
    if totals is None:
        return None
    return int(totals().get(EVENT, {}).get("count", 0))


def read(obs):
    before, after = obs["snapshots"]["tasks_speculated"]
    if before is None or after is None:
        return None
    return float(after - before)
