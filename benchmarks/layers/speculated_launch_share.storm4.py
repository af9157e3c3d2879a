"""planner/pipeline: as ``speculated_launch_share``, over the
launches of every chip: launches dispatched ahead of an unread launch
of their own group.  Two groups a chip are laid out so that this reads
0."""

from benchmarks.layers import _twin

read = _twin.of("speculated_launch_share")
