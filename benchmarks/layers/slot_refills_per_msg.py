"""planner/pipeline: objects a running solve took into a freed slot
(``pow_pipeline_refills_total``) over messages published.  Near 2 where
a send's ack and message both enter the device that way, 0 where every
object waits for a solve to start."""

from benchmarks.layers._spans import grown


def read(window):
    refills = grown(window, "pow_pipeline_refills_total")
    published = len(window.published)
    if refills is None or not published:
        return None
    return refills / published
