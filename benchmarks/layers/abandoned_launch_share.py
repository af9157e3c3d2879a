"""planner/pipeline: launches dispatched ahead and never fetched
(``pow_pipeline_abandoned_launches_total``) over all launches
(``pow_pipeline_launches_total``), both grown in the window.  The
device still runs an abandoned launch up to its first hit."""

from benchmarks.layers._spans import grown


def read(window):
    launches = grown(window, "pow_pipeline_launches_total")
    if not launches:
        return None
    abandoned = grown(window, "pow_pipeline_abandoned_launches_total")
    return 100.0 * (abandoned or 0.0) / launches
