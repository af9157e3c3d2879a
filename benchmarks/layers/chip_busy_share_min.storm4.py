"""device: as ``chip_busy_share_min``, at the highest object rate the
host is asked for: the least busy chip's busy share of the traced
window.  A chip that waits for the one interpreter shows here."""

from benchmarks.layers import _twin

read = _twin.of("chip_busy_share_min")
