"""planner/pipeline: share of the traced window in which a chip ran
nothing because nothing was left to search on it (under
``pow.lane.starved`` of that chip): every object of its launch groups
solved, the queue brought nothing, other chips still searching.  The
planes' mean."""

from benchmarks.layers._lanes import idle_share


def read(window):
    return idle_share(window, "starved")
