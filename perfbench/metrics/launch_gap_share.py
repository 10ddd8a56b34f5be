"""Share of the first chip's idle time in the traced window that lies under
a launch of the program: under any ``launch:<program>`` annotation of the
host planes (``compile/governor.py`` ``call_with`` opens one around every
jitted call), whatever runtime event runs inside it. The trace reduction
names a gap by the INNERMOST host event over it (``Allocate``,
``DoEnqueueProgram``); this takes the outermost, the program's own. With
``unnamed_idle_share`` and the ``device.block`` seconds it says how the idle
time splits between launching, waiting and Python between the two. A trace
without such annotations gives nothing to read."""

import _mesh

UNIT = "%"
LAUNCH = "launch:"


def _overlap(a, b) -> int:
    """Nanoseconds covered by both of two sorted lists of disjoint
    ``[start, end)`` intervals."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def read(obs):
    got = _mesh.chip_lines(obs)
    if got is None:
        return None
    chips, window, _ = got
    launches = [ev for p in obs["planes"]
                if not p["name"].startswith("/device:")
                for ln in p["lines"] for ev in ln["events"]
                if ev[0].startswith(LAUNCH)]
    if not launches:
        return None
    busy = _mesh.clipped(chips[0], window)
    idle, edge = [], window[0]
    for s, e in busy + [[window[1], window[1]]]:
        if s > edge:
            idle.append([edge, s])
        edge = max(edge, e)
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    return 100.0 * _overlap(idle, _mesh.clipped(launches, window)) / idle_ns
