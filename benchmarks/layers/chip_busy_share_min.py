"""device: the least busy chip's busy share of the traced window: for
each device plane of the recorded trace, the union of its operation
intervals inside the window over the window's length, and of those the
smallest.  ``device_idle_share`` averages the planes; a chip that runs
dry behind the host's one loop shows here."""

from benchmarks import tracereduce
from benchmarks.stats import union_seconds


def read(window):
    trace = window.notes.get("recorded_trace")
    if trace is None:
        return None
    w0, w1 = tracereduce.window_of(trace)
    shares = []
    for events in trace["device"].values():
        op_line = tracereduce._pick_line(events, tracereduce.OP_LINES)
        if op_line is None:
            continue
        busy = union_seconds(
            (max(s, w0), min(s + d, w1)) for line, _name, s, d in events
            if line == op_line and s < w1 and s + d > w0)
        shares.append(100.0 * busy / (w1 - w0))
    return min(shares) if shares else None
