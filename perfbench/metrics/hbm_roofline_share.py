"""The least time the chip's memory could take to stream the columns the
cell's queries read (``bytes_model.py`` over the peak in ``peaks.json``), as
a share of the time the device was busy on them. Bandwidth bounds these
queries: they do a few operations a byte."""

UNIT = "%"


def read(obs):
    t = obs["trace"]
    if not t or not t["queries"] or t["busy_s"] <= 0:
        return None
    kind = obs["device"]["kind"]
    if kind not in obs["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    per_round = sum(obs["query_bytes"][q] for q in obs["cell"]["traffic"]["round"])
    rounds = t["queries"] / len(obs["cell"]["traffic"]["round"])
    least_s = per_round * rounds / obs["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
