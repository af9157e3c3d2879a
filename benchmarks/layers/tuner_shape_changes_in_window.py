"""planner/pipeline: times the program's autotuner asked for another
chunk count than before, inside the window
(``pow_autotune_shape_changes_total``, every kind).  Must be 0: each
new chunk count is a program to trace, lower and compile, and one the
chip may refuse."""

from benchmarks.layers._spans import grown


def read(window):
    return grown(window, "pow_autotune_shape_changes_total")
