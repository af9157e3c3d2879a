"""Messages (or broadcasts) published in the window over its length;
the window runs until the last sweep it started has been published."""

from benchmarks.stats import rate


def read(window):
    return rate(len(window.published), window.seconds)
