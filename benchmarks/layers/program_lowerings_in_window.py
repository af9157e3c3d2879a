"""planner/pipeline: programs lowered inside the window as the program
itself counts them (``jax_compile_events_total{phase="lower"}``, fed
by JAX's monitoring event).  Must be 0, and equal to
``compiles_in_window``, which the benchmark counts from outside."""

from benchmarks.layers._spans import grown


def read(window):
    return grown(window, "jax_compile_events_total", ("lower",))
