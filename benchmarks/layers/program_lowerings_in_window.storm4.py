"""planner/pipeline: as ``program_lowerings_in_window``, where the
kernel is lowered and compiled once a chip: programs lowered inside
the window as the program itself counts them.  Must be 0."""

from benchmarks.layers import _twin

read = _twin.of("program_lowerings_in_window")
