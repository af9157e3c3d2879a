"""planner/pipeline: of the solves of the window whose one object was
laid out over several chips (``pow_pipeline_lone_wins_total``, credited
once a solve with the lane whose launch found the nonce), the share
won by another chip than the one that searches the object's own range
(lane 0).  An even share-out over four chips reads 75; 0 means the
other chips never get there first.  None where the program has no such
counter or no such solve ended in the window."""

from benchmarks.layers._spans import grown


def read(window):
    total = grown(window, "pow_pipeline_lone_wins_total")
    if not total:
        return None
    own = grown(window, "pow_pipeline_lone_wins_total", ("0",))
    return 100.0 * (total - own) / total
