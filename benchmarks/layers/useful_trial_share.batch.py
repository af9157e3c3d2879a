"""kernels: trials the published nonces needed over trials the device
computed, in cells where the batch program does the work.  The rest is
objects that search on in a launch dispatched before their hit was
harvested, and the one step a solved or pad slot costs per launch."""

from benchmarks.layers._kernels import useful_trial_share


def read(window):
    return useful_trial_share(window)
