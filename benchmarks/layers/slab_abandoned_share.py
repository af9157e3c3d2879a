"""planner/pipeline: slabs of the single-object loop
(``ops/sha512_pallas.solve``) dispatched ahead and left unfetched when
the slab before them hit (``pow_pipeline_abandoned_launches_total``,
``kind="slab"``) over all its slabs (``pow_pipeline_launches_total``,
``kind="slab"``), both grown in the window.  The device still runs an
abandoned slab up to its first hit."""

from benchmarks.layers._spans import grown

SLAB = ("slab",)


def read(window):
    launches = grown(window, "pow_pipeline_launches_total", SLAB)
    if not launches:
        return None
    abandoned = grown(window, "pow_pipeline_abandoned_launches_total",
                      SLAB)
    return 100.0 * (abandoned or 0.0) / launches
