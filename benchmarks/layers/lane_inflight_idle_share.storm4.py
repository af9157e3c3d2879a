"""planner/pipeline: share of the traced window in which a chip ran
nothing although its lane had a launch in flight, under a standing
queue on four chips: the planes' mean of the idle that neither
``pow.lane.turn`` nor ``pow.lane.starved`` of that chip covers.  The
cell's one solve outlives the profiler's session, so the window is
taken to lie inside it (``_lanes_fed``); ``lane_inflight_idle_share``
needs the solve's span in the trace and has none to read here."""

from benchmarks.layers._lanes_fed import idle_share


def read(window):
    return idle_share(window, "inflight")
