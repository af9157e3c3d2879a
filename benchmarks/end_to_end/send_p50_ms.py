"""Median send latency of the window's sends (see ``_latency``)."""

from benchmarks.end_to_end._latency import latency_ms


def read(window):
    return latency_ms(window, 50)
