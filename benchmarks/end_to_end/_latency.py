"""Send latency: from the ``send_message()`` / ``send_broadcast()``
call to the generator's poll that first saw the send's final status
(``msgsent``, ``broadcastsent``), over every send submitted in the
window.  A send that was never published has no latency and fails the
run (``check.verify`` counts it ``failed``), so none is dropped in
silence.

Only under the ``closed_loop`` generator, which times every send of a
sweep by itself.  Under ``backlog`` the outbox is filled before the
window, so submit-to-sent is a place in the queue and depends on where
the window was cut: no metric there, not a queue position under a
latency's name."""

from benchmarks.stats import percentile


def latencies_ms(window) -> list[float]:
    if window.bench.traffic["generator"] != "closed_loop":
        return []
    return [(s.t_done - s.t_submit) * 1e3 for s in window.published]


def latency_ms(window, q: float):
    times = latencies_ms(window)
    if not times:
        return None
    return percentile(times, q)
