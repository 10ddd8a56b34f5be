"""Backend compiles inside the measured window, reads from the disk cache
included. Should be 0: the warm-up runs until the program stops compiling."""

UNIT = "programs"


def read(obs):
    return float(obs["window"]["counters"]["backend_compiles"])
