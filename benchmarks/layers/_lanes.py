"""Shared arithmetic of the three readers that split the device's idle
time inside a solve by the state of the idle chip's own lane
(``benchmarks/lanereduce.py``).  None where the program keeps no lane
states (one older than ``pow_pipeline_lane_seconds_total``), so the
metric is left out of the line; a program that keeps them and opened
no interval in the window reads 0 for ``turn`` and ``starved``."""

from benchmarks import lanereduce
from benchmarks.layers._spans import grown


def idle_share(window, state: str):
    if grown(window, "pow_pipeline_lane_seconds_total") is None:
        return None
    red = lanereduce.for_window(window)
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * red["idle_by_state"][state] / red["window_s"]
