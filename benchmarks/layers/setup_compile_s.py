"""entry: seconds JAX spent tracing, lowering and compiling (or
loading from the persistent cache) before the window began
(``jax_compile_seconds_total``, all phases)."""

from benchmarks.layers._spans import at_window_start


def read(window):
    return at_window_start(window, "jax_compile_seconds_total")
