"""entry: seconds JAX spent tracing programs and lowering them before
the window began (``jax_compile_seconds_total``, phases ``trace`` and
``lower`` only): what a start pays whether the persistent compile
cache holds the executable or not, and what a persisted program
(``core/programcache``) takes away."""

FAMILY = "jax_compile_seconds_total"
PHASES = (("trace",), ("lower",))


def read(window):
    values = [v for (fam, labels), v in window.counters.before.items()
              if fam == FAMILY and not isinstance(v, tuple)
              and labels[:1] in PHASES]
    return sum(values) if values else None
