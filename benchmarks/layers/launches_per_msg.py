"""planner/pipeline: kernel launches of the window (the benchmark's
own count, abandoned speculative launches too) per message published."""


def read(window):
    n = len(window.published)
    return len(window.launches) / n if n else None
