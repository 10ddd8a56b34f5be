"""Share of the device's idle time in the traced window that lies under no
host event at all: the seconds of the trace reduction's ``idle_gaps`` entries
that end in ``no_traced_host_event``, over ``window_s - busy_s``. The reduction
keeps the ten longest entries only, so this is a lower bound. Where the
program's spans are host events of the trace (profiler annotations), a sleeping
poll loop has a name and this share falls."""

UNIT = "%"
UNNAMED = "no_traced_host_event"


def read(obs):
    t = obs["trace"]
    if not t:
        return None
    idle = t["window_s"] - t["busy_s"]
    if idle <= 0:
        return None
    unnamed = sum(seconds for name, seconds in t["idle_gaps"]
                  if name.endswith(UNNAMED))
    return 100.0 * unnamed / idle
