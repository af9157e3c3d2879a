"""planner/pipeline: as ``live_slot_share``, over the launches of every
chip: slots that searched over all slots launched.  ``MAX_IN_FLIGHT``
256 broadcasts over eight groups of 64 slots read 50."""

from benchmarks.layers import _twin

read = _twin.of("live_slot_share")
