"""Process start to the start of the window: data made or found, ingest,
compiling or reading the compile cache, warm-up. The reference's work comes
after the window and is not in it."""

UNIT = "s"


def read(obs):
    return obs["setup"]["seconds"]
