"""What the two readers of a shuffling task's write share: the numbers of
the program's ``shuffle.write`` event (one a task that wrote shuffled
output: ``fan_out`` destinations, ``batches`` produced, ``slices`` =
batches x fan-out handed to ``ipc.batch_to_arrow``), as the task's
``ShuffleWrite`` metrics row carries them to the scheduler and the client
gets them back with the query's stages. The event itself lives in a ring
that one q3 of this cell turns over three times, so the readers take the
row, which sums over a stage's tasks. A program whose row has no
``shuffle_slices``, as one older than the event has not, gives nothing to
read."""

from __future__ import annotations

from _common import records


def of_stages(stages):
    """``{"tasks", "fan_out", "batches", "slices"}`` of one query's
    ``stages`` (as ``last_query_metrics()`` gives them): sums over the
    tasks that wrote shuffled output; None where no row says
    ``shuffle_slices``."""
    q = {"tasks": 0, "fan_out": 0, "batches": 0, "slices": 0}
    for st in (stages or {}).values():
        for op in st.get("operators") or []:
            m = op.get("metrics") or {}
            if op.get("operator") != "ShuffleWrite" \
                    or "shuffle_slices" not in m:
                continue
            q["tasks"] += int(st.get("num_tasks", 0))
            for k in ("fan_out", "batches", "slices"):
                q[k] += int(m.get("shuffle_" + k, 0))
    return q if q["tasks"] else None


def per_query(obs):
    """``of_stages`` of every recorded query of the window that shuffled;
    None where there is none."""
    got = [of_stages(r.get("stages")) for r in records(obs)]
    return [q for q in got if q is not None] or None
