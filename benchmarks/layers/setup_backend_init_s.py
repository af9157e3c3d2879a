"""entry: wall seconds of the process's first backend initialisation,
as ``core/jaxsetup.setup_jax`` timed it (``jax_backend_init_seconds``)."""

from benchmarks.layers._spans import at_window_start


def read(window):
    return at_window_start(window, "jax_backend_init_seconds")
