"""planner/pipeline: as ``chip_launch_share_max``: of the launches
the pipeline dispatched in the window, the share of the chip that took
most.  25 is even on four chips."""

from benchmarks.layers import _twin

read = _twin.of("chip_launch_share_max")
