"""95th percentile over all the window's latencies: only where the window
holds at least 200 queries, so that ten lie beyond it."""

from _common import percentile

UNIT = "s"
MIN_SAMPLES = 200


def read(obs):
    seconds = [q["seconds"] for q in obs["window"]["queries"]]
    return percentile(seconds, 0.95) if len(seconds) >= MIN_SAMPLES else None
