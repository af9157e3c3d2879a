"""send queue: mean time from a send's request for a proof of work to
its nonce, as the sender sees it (``worker_pow_wait_seconds`` sum over
count, grown in the window): queue, solve and the way back."""


def read(window):
    total, count = window.counters.hist("worker_pow_wait_seconds")
    return total / count * 1e3 if count else None
