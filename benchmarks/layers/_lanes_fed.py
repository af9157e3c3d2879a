"""Shared arithmetic of the lane readers of a cell whose ONE solve is
fed for the whole run (a standing outbox: the ``backlog`` generator).
That solve begins in warm-up and ends after the check, and the
profiler records only what begins AND ends inside its session, so the
trace of such a cell holds no solve span at all: ``lanereduce`` then
gives every idle second to "between solves" and reads ``inflight`` as
minus the other two.  Here the traced window is taken to lie inside
the solve, as it does while the outbox stands: a chip's idle under its
own lane's ``pow.lane.turn`` or ``pow.lane.starved`` is that state's,
and ALL the rest of its idle is ``inflight`` (a launch of its lane out
and not yet read).  The three add up to ``device_idle_share``.  Should
the solve end inside the window (an outbox that ran dry, a sender that
stalled), the lane is ``starved`` first, and the idle after the solve's
end would read as ``inflight``: ``starved`` above 0 says the reading no
longer stands.  None where the program keeps no lane states, as
``_lanes``."""

from benchmarks import lanereduce
from benchmarks.layers._spans import grown


def idle_share(window, state: str):
    if grown(window, "pow_pipeline_lane_seconds_total") is None:
        return None
    red = lanereduce.for_window(window)
    if red is None or red["window_s"] <= 0 or not red["chips"]:
        return None
    chips = list(red["chips"].values())
    if state == "inflight":
        seconds = [row["idle_s"] - row["turn"] - row["starved"]
                   for row in chips]
    else:
        seconds = [row[state] for row in chips]
    return 100.0 * sum(seconds) / len(chips) / red["window_s"]
