"""Share of the ``all_to_all`` seconds in which the same chip ran no other
operation: collective time that nothing hides, over the cell's chips."""

import _mesh

UNIT = "%"


def read(obs):
    got = _mesh.exchange_seconds(obs)
    if got is None:
        return None
    _, _, per_chip, _ = got
    whole = exposed = 0.0
    for coll, rest in per_chip:
        whole += _mesh.seconds(coll)
        # collective time covered by another operation of the same chip
        hidden, k = 0, 0
        for s, e in coll:
            while k < len(rest) and rest[k][1] <= s:
                k += 1
            j = k
            while j < len(rest) and rest[j][0] < e:
                hidden += min(e, rest[j][1]) - max(s, rest[j][0])
                j += 1
        exposed += _mesh.seconds(coll) - hidden / 1e9
    return 100.0 * exposed / whole if whole > 0 else None
