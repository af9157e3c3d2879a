"""kernels: as ``useful_trial_share.queue``, where one object's nonce
space is shared out over the chips: trials the searches needed, as the
program credits them a harvest (a miss its slab, the hit up to its
nonce), over trials the chips computed.  The rest is the share-out's
price: what the other chips search on after the first hit, to their
own hit or their launch's end.  (``useful_trial_share.slab`` reads the
published nonces, and a chip's share begins quarters of 2**64 apart.)"""

from benchmarks.layers import _twin

read = _twin.of("useful_trial_share.queue")
