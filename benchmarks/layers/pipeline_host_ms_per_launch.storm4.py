"""planner/pipeline: as ``pipeline_host_ms_per_launch.queue``
(``pow.launch`` and ``pow.harvest`` spans inside the window, per launch
counted), where one host loop launches for four chips and each harvest
resolves some dozen broadcasts."""

from benchmarks.layers import _twin

read = _twin.of("pipeline_host_ms_per_launch.queue")
