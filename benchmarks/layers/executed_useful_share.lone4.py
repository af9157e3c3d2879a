"""kernels: where one object is searched by every chip of the host as
ONE program whose kernels stop at the first hit, trials the searches
needed (``pow_pipeline_needed_trials_total``, ``kind="slab"``: the
winner's up to its nonce, every other lane's up to the winner's step, a
launch nobody won whole) over trials the chips computed
(``pow_pipeline_executed_trials_total``, ``kind="slab"``: the grid
steps every lane's row says it ran), both grown in the window.  The
rest is what the losers ran past the winner's step.  Counted by the
program from what every lane reports, so it needs no launch log: where
a lane is a launch of its own the losers' unread launches are in
neither count, and this reads what was read.  None where the program
has no such series or nothing was computed."""

from benchmarks.layers._spans import grown

SLAB = ("slab",)


def read(window):
    executed = grown(window, "pow_pipeline_executed_trials_total", SLAB)
    needed = grown(window, "pow_pipeline_needed_trials_total", SLAB)
    if not executed or needed is None:
        return None
    return 100.0 * needed / executed
