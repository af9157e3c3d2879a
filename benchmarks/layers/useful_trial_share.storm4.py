"""kernels: as ``useful_trial_share.queue``, over the launches of every
chip: trials the searches needed, as the program credits them a
harvest, over trials the devices computed.  256 broadcasts in flight
over eight groups of 64 slots leave half of a launch's slots dead, one
tile each."""

from benchmarks.layers import _twin

read = _twin.of("useful_trial_share.queue")
