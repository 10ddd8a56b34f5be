"""Compile seconds during set-up: compiling in a checkout's first run,
reading programs back from the disk cache in every later one."""

UNIT = "s"


def read(obs):
    return obs["setup"]["counters"]["compile_seconds"]
