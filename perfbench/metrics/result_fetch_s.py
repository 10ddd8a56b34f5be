"""Seconds a query spent bringing its result to the client, from the program's
latency ledger: ``result_transfer`` (served: the result partitions' bytes
fetched from the executors; standalone: the device-to-host copy, after the
wait for the device, which is a ``device.block`` span and not in here) plus
``host_decode`` (bytes to host arrays to the DataFrame)."""

from _common import phase_mean

UNIT = "s"


def read(obs):
    return phase_mean(obs, "result_transfer", "host_decode")
