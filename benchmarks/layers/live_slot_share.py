"""planner/pipeline: slots of the window's launches that searched
(``pow_pipeline_slots_total{state="live"}``) over all slots launched.
The rest were solved and not yet refilled, or pad, and cost one step
each."""

from benchmarks.layers._queue import share


def read(window):
    return share(window, "pow_pipeline_slots_total", {1: "live"})
