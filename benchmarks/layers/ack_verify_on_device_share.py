"""receive verify: incoming proof-of-work checks the process ran on the
device (``pow_verify_total{path="device"}``) over all it ran, both
nodes' (the recipient's stay on the host).  A reading, not a target:
returning acks arrive a few a tick, under ``min_device_batch``, and
then take the host's path."""

from benchmarks.layers._queue import share


def read(window):
    return share(window, "pow_verify_total", {0: "device"})
