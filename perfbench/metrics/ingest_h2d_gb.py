"""Host bytes handed to upload during set-up. (The program's h2d SECONDS time
the enqueue, not the transfer, and are not read.)"""

UNIT = "GB"


def read(obs):
    return obs["setup"]["counters"]["h2d_bytes"] / 1e9
