"""kernels: trials the batch program (``pallas_batch_search``) computed
in the launches of the traced window over its summed device time
there, in cells where a solve is a stream (the launches that were in
flight when the window began and those in flight when it ended stand
in for each other)."""

from benchmarks.layers._queue import kernel_mhash_per_s


def read(window):
    return kernel_mhash_per_s(window, "batch")
