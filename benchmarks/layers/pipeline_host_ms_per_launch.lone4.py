"""planner/pipeline: as ``pipeline_host_ms_per_launch`` (``pow.launch``
and ``pow.harvest`` spans inside the window, per launch counted), where
one host loop launches a lone object on every chip in one turn and
harvests the first that comes in."""

from benchmarks.layers import _twin

read = _twin.of("pipeline_host_ms_per_launch")
