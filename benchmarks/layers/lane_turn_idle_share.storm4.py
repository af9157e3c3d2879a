"""planner/pipeline: share of the traced window in which a chip ran
nothing while its lane's queue was empty and the host's one loop had
yet to come round to it, under a standing queue on four chips: the
planes' mean (``_lanes_fed``).  What one interpreter feeding four chips
some 110 objects a second costs the chips shows here."""

from benchmarks.layers._lanes_fed import idle_share


def read(window):
    return idle_share(window, "turn")
