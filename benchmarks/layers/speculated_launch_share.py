"""planner/pipeline: launches dispatched ahead of an unread launch of
their own group (``pow_pipeline_speculation_total{decision="launched"}``)
over all launches (``pow_pipeline_launches_total``).  Objects that hit
in the unread launch search on in the one dispatched ahead."""

from benchmarks.layers._queue import share


def read(window):
    return share(window, "pow_pipeline_speculation_total",
                 {1: "launched"}, whole="pow_pipeline_launches_total")
