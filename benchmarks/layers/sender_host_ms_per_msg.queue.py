"""sender: host milliseconds of ``sender.sign``, ``sender.encrypt``
and ``sender.publish`` spans inside the window, per message published,
in cells where sends are admitted as slots come free: the crypto of one
send runs beside the harvests that resolve the others."""

from benchmarks.layers._spans import span_seconds


def read(window):
    seconds = span_seconds(
        window, ("sender.sign", "sender.encrypt", "sender.publish"))
    n = len(window.published)
    if seconds is None or not n:
        return None
    return seconds * 1e3 / n
