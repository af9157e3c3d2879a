"""planner/pipeline: of the lanes that did not win their launch
(``pow_pipeline_lone_lanes_total``, grown in the window, every outcome
but ``won``), the share that left on the winner's flag (``cancelled``)
and neither hit on its own (``own_hit``: in the winner's step, or
before the flag reached it) nor ran every step of its launch
(``ran_out``): how often the mechanism engages.  None where the
program has no such counter or no such launch was read in the
window."""

from benchmarks.layers._spans import grown

FAMILY = "pow_pipeline_lone_lanes_total"


def read(window):
    lanes = grown(window, FAMILY)
    if not lanes:
        return None
    losers = lanes - grown(window, FAMILY, ("won",))
    if not losers:
        return None
    return 100.0 * grown(window, FAMILY, ("cancelled",)) / losers
