"""90th percentile of the send latency (see ``_latency``).  Not the
99th: the smallest cell has some 480 sends a window, 48 beyond the
90th percentile and 5 beyond a 99th, where ten are wanted."""

from benchmarks.end_to_end._latency import latency_ms


def read(window):
    return latency_ms(window, 90)
