"""sender: share of the time between the window's two registry
snapshots in which the sender's one crypto thread was inside a job
(``cryptopool_busy_seconds_total{pool="sender"}``): every broadcast's
signature and encryption.  100 is a thread that never waits for work.
The second snapshot is taken where the measured span ends: in a traced
run after the device's launches were read
(``window.trace["window_s"]``), else when the last sweep returned
(``window.seconds``); jobs that ran in between are included, over the
time they ran in.  None where the program has no such series."""

from benchmarks.layers._spans import grown


def read(window):
    busy = grown(window, "cryptopool_busy_seconds_total", ("sender",))
    between = (window.seconds if window.trace is None
               else window.trace["window_s"])
    if busy is None or between <= 0:
        return None
    return 100.0 * busy / between
