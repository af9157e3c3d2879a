"""Shared arithmetic of the readers for cells in which a solve is a
stream (``burst_send_64``, ``queue_1k``).

Such a window need not begin and end with an idle device: in
``queue_1k`` one solve outlives it, launches go on while the harness
stops the profiler, and the node goes on publishing its backlog until
the check is over.  So the launch log is cut to the traced window (the
span the registry's two snapshots lie at the ends of), and trials that
served are what the program credits a harvest
(``pow_pipeline_needed_trials_total``, grown between the snapshots: a
slot that missed, its slab; one that hit, up to its winning nonce) and
neither the nonces of whatever had been published when the check ran
nor an object's whole search credited at its hit, much of which lies
before the window.  A launch's needed trials are at most what it
computed, so the share cannot pass 100 but for the launches in flight
at the window's two ends.
"""

import dataclasses

from benchmarks.layers import _kernels
from benchmarks.layers._spans import grown


def cut_to_trace(window):
    """``window`` with the launches dispatched inside the traced
    window only; None without a trace.  The window began ``seconds``
    before its last send was seen published."""
    ends = [s.t_done for s in window.published]
    if window.trace is None or not ends:
        return None
    t0 = max(ends) - window.seconds
    t1 = t0 + window.trace["window_s"]
    return dataclasses.replace(window, launches=[
        r for r in window.launches if r["t"] <= t1])


def kernel_mhash_per_s(window, program: str):
    cut = cut_to_trace(window)
    return None if cut is None else _kernels.kernel_mhash_per_s(cut,
                                                                program)


def useful_trial_share(window):
    cut = cut_to_trace(window)
    if cut is None:
        return None
    computed = _kernels.computed_trials(cut)
    needed = grown(window, "pow_pipeline_needed_trials_total")
    if computed <= 0 or not needed:
        return None
    return 100.0 * needed / computed


def share(window, family: str, part: dict, whole: str | None = None):
    """Growth of the series of ``family`` whose labels contain ``part``
    (label position -> value) as a percentage of the growth of all of
    ``whole`` (``family`` itself by default); None where the program
    has no such family or nothing grew."""
    total = grown(window, whole or family)
    if not total:
        return None
    some = sum(v for labels, v in window.counters.delta(family).items()
               if all(labels[i] == want for i, want in part.items()))
    return 100.0 * some / total
