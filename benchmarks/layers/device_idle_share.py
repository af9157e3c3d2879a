"""device: share of the traced window in which no operation ran on the
device: 1 - union of the device's operation intervals / window."""


def read(window):
    if window.trace is None or window.trace["window_s"] <= 0:
        return None
    t = window.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
