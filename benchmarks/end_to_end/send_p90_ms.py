"""90th percentile of the send latency (see ``_latency``): a send is
about a second, so a window holds some tens of them and the 95th
percentile would rest on two or three samples."""

from benchmarks.end_to_end._latency import latency_ms


def read(window):
    return latency_ms(window, 90)
