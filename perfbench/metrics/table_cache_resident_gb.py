"""What the device table cache holds at the window's start: the bytes of
the scan partitions pinned on the device, which every scan of the window
is then served from (the configuration's third guarantee; its budget is
``BALLISTA_TABLE_CACHE_BUDGET_MB``). The buffers a cached plan keeps beside
them are not in it: ``peak_hbm_gb`` less this is what plans and programs
hold. A program without the counter gives nothing to read."""

UNIT = "GB"
KEY = "table_cache_resident_bytes"


def snapshot():
    from ballista_tpu.cache import cache_counters

    value = cache_counters().get(KEY)
    return None if value is None else int(value)


def read(obs):
    at_start, _ = obs["snapshots"]["table_cache_resident_gb"]
    return None if at_start is None else at_start / 1e9
