"""Queries completed in the window over its length. The window ends on a
round boundary, so every query counted ran whole inside it."""

UNIT = "queries/h"


def read(obs):
    done = len(obs["window"]["queries"])
    return 3600.0 * done / obs["window"]["seconds"] if done else None
