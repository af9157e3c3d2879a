"""planner/pipeline: share of the traced window in which the device
ran nothing although a solve was under way (inside a
``pow.solve_batch`` span): the pipeline's own gaps between launches."""

from benchmarks.layers._spans import idle_share


def read(window):
    return idle_share(window, "idle_in_solve_s")
