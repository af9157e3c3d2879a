"""Send latency: from the ``send_message()`` call to the message's
status ``msgsent``, over every message submitted in the window.  A
message that was never published has no latency and fails the run."""

from benchmarks.stats import percentile


def latency_ms(window, q: float):
    times = [(s.t_done - s.t_submit) * 1e3 for s in window.published]
    if not times:
        return None
    return percentile(times, q)
