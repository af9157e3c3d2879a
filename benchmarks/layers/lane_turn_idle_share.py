"""planner/pipeline: share of the traced window in which a chip ran
nothing while its lane's queue was empty and the host's loop had yet to
come round to it (under ``pow.lane.turn`` of that chip): the harvest of
what came in, the lanes asked before it, the launch itself.  The
planes' mean."""

from benchmarks.layers._lanes import idle_share


def read(window):
    return idle_share(window, "turn")
