"""kernels: as ``kernel_mhash_per_s.queue``, on a host of several
chips: the trials the batch program computed in the launches of the
traced window, on whichever chip, over its device time there averaged
over the chips (``tracereduce.reduce_trace``): all chips together."""

from benchmarks.layers import _twin

read = _twin.of("kernel_mhash_per_s.queue")
