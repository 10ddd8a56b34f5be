"""Scans the residency cache served from the device, as a share of all scans
in the window."""

UNIT = "%"


def snapshot():
    from ballista_tpu.cache import cache_counters

    cc = cache_counters()
    return int(cc["table_cache_hits"]), int(cc["table_cache_misses"])


def read(obs):
    (h0, m0), (h1, m1) = obs["snapshots"]["table_cache_hit_share"]
    scans = (h1 - h0) + (m1 - m0)
    return 100.0 * (h1 - h0) / scans if scans else None
