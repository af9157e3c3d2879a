"""kernels: the rate of the search kernel where one object is searched
by every chip of the host as ONE program whose kernels stop at the
first hit (``ops/sha512_ici.ici_search``), all chips together, as
``kernel_mhash_per_s.lone4`` read it of a launch a lane: the trials the
chips computed (``pow_pipeline_executed_trials_total``, ``kind="slab"``:
the grid steps every lane's row says it ran, grown in the window) over
the device seconds of that program's kernel in the trace (the
operations named ``ici_search`` among the window's ``device_ops``, the
planes' mean: ``tracereduce.reduce_trace``).  The kernel's seconds hold
what the flag costs it: the barrier at a launch's start, the read of a
semaphore at every step, the empty steps after a chip has left its
search, the handshake at the end; so this reads below the chips times
``kernel_mhash_per_s.slab`` by that cost, and a later PR that makes
the flag dearer shows here.  None without a trace, where no such
operation ran in the window (a program that launches a lane at a time,
the parent) or where the program counted no such trials."""

from benchmarks.layers._spans import grown

NEEDLE = "ici_search"
SLAB = ("slab",)


def read(window):
    if window.trace is None:
        return None
    seconds = sum(secs for name, secs in window.trace.get("device_ops", ())
                  if NEEDLE in name)
    trials = grown(window, "pow_pipeline_executed_trials_total", SLAB)
    if seconds <= 0 or not trials:
        return None
    return trials / seconds / 1e6
