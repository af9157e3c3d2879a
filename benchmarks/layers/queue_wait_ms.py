"""send queue: mean time a solve request waited in PowService's
coalescing queue before its batch launched
(``pow_queue_wait_seconds`` sum over count, grown in the window)."""


def read(window):
    total, count = window.counters.hist("pow_queue_wait_seconds")
    return total / count * 1e3 if count else None
