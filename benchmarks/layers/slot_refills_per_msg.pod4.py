"""planner/pipeline: as ``slot_refills_per_msg``, on whichever chip
freed the slot: objects a running solve took into a freed slot over
messages published.  Near 2 where a send's ack and message both enter
the devices that way."""

from benchmarks.layers import _twin

read = _twin.of("slot_refills_per_msg")
