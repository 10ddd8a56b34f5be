"""The one traffic generator: a traffic file's parameters and a seed in, each
stream's rounds of query names out.

Every seed gives every round the same set of queries (so the same work), in
another order. A traffic file says ``arrival`` (``closed``: a stream sends
its next query when the last one's answer has come), ``streams``, the
``round`` (query names, each with its files in ``queries/``) and
``parameters`` (``fixed``: the SQL text is the same in every execution). The
seed permutes the order within each round."""

from __future__ import annotations

import numpy as np

ARRIVALS = ("closed",)
PARAMETERS = ("fixed",)


def check(traffic: dict) -> None:
    for key, allowed in (("arrival", ARRIVALS), ("parameters", PARAMETERS)):
        if traffic.get(key) not in allowed:
            raise ValueError(f"traffic {key} {traffic.get(key)!r}: the "
                             f"generator knows {allowed}")
    if int(traffic["streams"]) < 1 or not traffic["round"]:
        raise ValueError("traffic needs streams >= 1 and a round of queries")


def rounds(traffic: dict, seed: int, stream: int):
    """Endless rounds for one stream: each a list of query names."""
    rng = np.random.default_rng([int(seed), int(stream)])
    names = list(traffic["round"])
    while True:
        yield [names[i] for i in rng.permutation(len(names))]
