"""kernels: trials the searches needed, as the program credits them a
harvest (``pow_pipeline_needed_trials_total``), over trials the device
computed in the launches of the window, in cells where a solve is a
stream.  The rest is the grid step past each hit, objects that search
on in a launch dispatched before their hit was harvested, and the one
step a solved or pad slot costs per launch."""

from benchmarks.layers._queue import useful_trial_share


def read(window):
    return useful_trial_share(window)
