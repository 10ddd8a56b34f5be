"""The allocator's peak on the fullest chip, after the window."""

UNIT = "GB"


def read(obs):
    return obs["peak_bytes"] / 1e9 if obs["peak_bytes"] else None
