"""How unevenly the work lies over the cell's chips: the busiest chip's
busy seconds less the idlest chip's, over the mean, in the traced rounds
(busy as ``device_busy_s_per_query`` takes it, chip by chip). 0 is an even
spread; 400 on four chips is one chip doing everything."""

import _mesh

UNIT = "%"


def read(obs):
    got = _mesh.chip_lines(obs)
    if got is None or len(got[0]) < 2:
        return None
    chips, window, _ = got
    busy = [_mesh.seconds(_mesh.clipped(ops, window)) for ops in chips]
    mean = sum(busy) / len(busy)
    return 100.0 * (max(busy) - min(busy)) / mean if mean > 0 else None
