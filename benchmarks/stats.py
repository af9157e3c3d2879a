"""Percentile and window arithmetic of the end-to-end metrics."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks — numpy's default, without numpy."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError("q must lie in 0..100")
    pos = (len(data) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = math.ceil(pos)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)


def rate(count: int, seconds: float) -> float:
    """Work completed per second over the whole window."""
    if seconds <= 0:
        raise ValueError("a window has a positive length")
    return count / seconds


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end_so_far = None
    for start, end in sorted(intervals):
        if end_so_far is None or start > end_so_far:
            total += end - start
            end_so_far = end
        elif end > end_so_far:
            total += end - end_so_far
            end_so_far = end
    return total


def gaps(intervals, window: tuple[float, float]):
    """The idle gaps ``(start, end)`` that ``intervals`` leave inside
    ``window``."""
    out = []
    cursor = window[0]
    for start, end in sorted(intervals):
        start, end = max(start, window[0]), min(end, window[1])
        if end <= start:
            continue
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < window[1]:
        out.append((cursor, window[1]))
    return out
