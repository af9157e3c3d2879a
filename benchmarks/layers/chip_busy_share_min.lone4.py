"""device: as ``chip_busy_share_min``, in a cell whose chips idle through
the host's work between two solves: the least busy chip's busy share of
the traced window."""

from benchmarks.layers import _twin

read = _twin.of("chip_busy_share_min")
