"""kernels: as ``kernel_mhash_per_s.queue``, where a standing queue of
broadcasts keeps four chips searching: the trials the batch program
computed in the launches of the traced window, on whichever chip, over
its device time there averaged over the chips: all chips together."""

from benchmarks.layers import _twin

read = _twin.of("kernel_mhash_per_s.queue")
