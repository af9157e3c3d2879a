"""Shared arithmetic of the kernels' readers.

Trials computed are the benchmark's own count: for every launch of the
window, the grid steps it really ran (read from its output, see
``kernel_work``) times the trials of one step of its shape.  Kernel
time is the summed device time of the program's events in the trace.
Trials needed are each published object's winning nonce plus one (a
search starts at nonce 0), read from the objects themselves.
"""


def computed_trials(window, program=None) -> int:
    return sum(r["trials"] for r in window.launches
               if program is None or r["program"] == program)


def kernel_mhash_per_s(window, program: str):
    if window.trace is None:
        return None
    seconds = window.trace["kernel_s"].get(program, 0.0)
    trials = computed_trials(window, program)
    if seconds <= 0 or trials <= 0:
        return None
    return trials / seconds / 1e6


def useful_trial_share(window):
    computed = computed_trials(window)
    if computed <= 0:
        return None
    return 100.0 * window.verdict["needed_trials"] / computed
