"""planner/pipeline: share of the traced window in which a chip ran
nothing inside a solve although its lane had a launch in flight: the
device had finished it (or not begun it) and the host had not read it
yet.  The planes' mean of the idle inside a solve span that neither
``pow.lane.turn`` nor ``pow.lane.starved`` of that chip covers.  With
``lane_turn_idle_share`` and ``lane_starved_idle_share`` it adds up to
``idle_in_solve_share``."""

from benchmarks.layers._lanes import idle_share


def read(window):
    return idle_share(window, "inflight")
