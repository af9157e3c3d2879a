"""sender: share of the traced window in which the device ran nothing
and no solve was under way (no ``pow.solve_batch`` span open): signing,
encrypting, the coalescing window, publishing.  With
``idle_in_solve_share`` it adds up to ``device_idle_share``."""

from benchmarks.layers._spans import idle_share


def read(window):
    return idle_share(window, "idle_between_solves_s")
