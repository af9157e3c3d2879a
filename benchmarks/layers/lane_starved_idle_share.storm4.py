"""planner/pipeline: share of the traced window in which a chip ran
nothing because its lane had nothing left to search, under a standing
queue on four chips: the planes' mean (``_lanes_fed``).  10,000
outstanding should keep it at 0; a sender that admits too slowly shows
here, and above 0 the window no longer lies inside one fed solve."""

from benchmarks.layers._lanes_fed import idle_share


def read(window):
    return idle_share(window, "starved")
