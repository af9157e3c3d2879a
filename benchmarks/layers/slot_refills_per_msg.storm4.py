"""planner/pipeline: as ``slot_refills_per_msg``, on whichever chip
freed the slot: objects a running solve took into a freed slot over
broadcasts published.  Near 1: one proof of work a broadcast."""

from benchmarks.layers import _twin

read = _twin.of("slot_refills_per_msg")
