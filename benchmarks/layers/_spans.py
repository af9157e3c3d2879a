"""Shared arithmetic of the readers that read the program's own spans
and the counters beside them.  Each returns None where the program has
no such span or counter (a program older than they are), so the metric
is left out of the line."""

from benchmarks import spanreduce


def idle_share(window, key: str):
    red = spanreduce.for_window(window)
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * red[key] / red["window_s"]


def span_seconds(window, names):
    """Summed seconds inside the window of the spans ``names``."""
    red = spanreduce.for_window(window)
    if red is None:
        return None
    return sum(red["span_s"].get(n, 0.0) for n in names)


def grown(window, family: str, labels=None):
    """Growth of counter ``family`` over the window (one series if
    ``labels`` is given, else all of them); None if the program has no
    such family."""
    if not any(fam == family for fam, _labels in window.counters.after):
        return None
    delta = window.counters.delta(family)
    if labels is not None:
        return delta.get(tuple(labels), 0.0)
    return sum(delta.values())


def at_window_start(window, family: str):
    """Summed value of ``family`` when the window began; None if the
    program has no such family."""
    values = [v for (fam, _labels), v in window.counters.before.items()
              if fam == family and not isinstance(v, tuple)]
    return sum(values) if values else None
