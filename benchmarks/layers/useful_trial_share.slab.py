"""kernels: trials the published nonces needed over trials the device
computed, in cells where the single-object slab program does the work.
The rest is speculative slabs dispatched ahead and abandoned."""

from benchmarks.layers._kernels import useful_trial_share


def read(window):
    return useful_trial_share(window)
