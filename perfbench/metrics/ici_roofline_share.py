"""The least time the interconnect could take to move the bytes that the
traced queries' hash-partitioned joins HAVE to move between chips
(``mesh_bytes.py``: from the data and the query, not from the program; each
chip sends its share at the peak of ``peaks_ici.json``), as a share of the
time the chips spent in ``all_to_all`` collectives."""

import json
import os

import _mesh

UNIT = "%"


def read(obs):
    import mesh_bytes
    import xplane

    got = _mesh.exchange_seconds(obs)
    if got is None:
        return None
    total, _, _, notes = got
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks_ici.json")) as fh:
        peaks = json.load(fh)
    kind = obs["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks_ici.json")
    chips = obs["cell"]["chips"]
    data_dir = mesh_bytes.data_dir_of(obs)
    per_query = {q: mesh_bytes.crossing_bytes(q, data_dir, chips)
                 for q in obs["cell"]["queries"]}
    crossing = sum(per_query[name[len(xplane.ANNOTATION):]]
                   for name, _, _ in notes)
    least_s = crossing / chips / peaks[kind]["ici_bytes_per_s"]
    return 100.0 * least_s / total
