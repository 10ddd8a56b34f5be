"""The least time the chip's memory could take to move the rows that the
traced queries' repartitions gathered (``repart_bytes.py``: every row of a
``repart.take`` event at the width its side has on the device, read once
and written once, over the peak in ``peaks.json``), as a share of the
device time of the ``jit_repart_take`` programs in the traced window.
Bandwidth bounds a gather of rows: it does no arithmetic. The takes of a
query are those its executions in the window recorded (a mean by query
name, all warm and alike), times the queries of that name in the trace. A
program without the event, or a trace without the program, gives nothing
to read."""

import re

import _mesh
import _plan_events

UNIT = "%"
PROGRAM = re.compile(r"^jit_repart_take(\(\d+\))?$")


def take_seconds(obs):
    """``(seconds of jit_repart_take programs on the first chip inside
    the traced window, the traced queries' annotations)``, or None."""
    import xplane

    got = _mesh.chip_lines(obs)
    if got is None:
        return None
    _, window, notes = got
    device = next(p for p in obs["planes"]
                  if xplane.DEVICE_PLANE.match(p["name"]))
    programs = [ev for ln in device["lines"]
                if ln["name"] == xplane.MODULES_LINE
                for ev in ln["events"] if PROGRAM.match(ev[0])]
    seconds = _mesh.seconds(_mesh.clipped(programs, window))
    return (seconds, notes) if seconds > 0 else None


def read(obs):
    import mesh_bytes
    import repart_bytes
    import xplane

    per_query = _plan_events.per_query(obs, "repart.take")
    got = take_seconds(obs)
    if per_query is None or got is None:
        return None
    seconds, notes = got
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    data_dir = mesh_bytes.data_dir_of(obs)
    moved = {}  # query name -> the bytes of each of its executions
    for q, events in per_query:
        moved.setdefault(q, []).append(repart_bytes.take_bytes(
            obs["cell"]["queries"][q], data_dir, events))
    a_query = {q: sum(v) / len(v) for q, v in moved.items()}
    traced = sum(a_query.get(name[len(xplane.ANNOTATION):], 0.0)
                 for name, _, _ in notes)
    least_s = traced / obs["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
