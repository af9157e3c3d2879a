"""send queue: mean objects coalesced into one ``solve_batch`` launch
(``pow_batch_size`` sum over count, grown in the window)."""


def read(window):
    total, count = window.counters.hist("pow_batch_size")
    return total / count if count else None
