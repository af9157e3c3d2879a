"""planner/pipeline: as ``lane_inflight_idle_share``, where every solve is
one object on every lane: the planes' mean of the idle inside a solve
by the idle chip's own lane state."""

from benchmarks.layers import _twin

read = _twin.of("lane_inflight_idle_share")
