"""kernels: as ``useful_trial_share.queue``, over the launches of every
chip: trials the searches needed, as the program credits them a
harvest, over trials the devices computed.  A queue on several chips
is laid out as at least two launch groups a chip, so more of its slots
are pad or solved, one step each a launch."""

from benchmarks.layers import _twin

read = _twin.of("useful_trial_share.queue")
