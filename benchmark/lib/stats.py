"""The arithmetic every metric shares: the median and the spread the
driver reads from a set of runs, and the interval arithmetic of the
trace reduction (``merge_intervals`` copied from ``obs/xprof.py``, so
that no later change to the program moves the yardstick)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def median(values: Sequence[float]) -> Optional[float]:
    """The plain median (mean of the middle two for an even count)."""
    window = sorted(values)
    n = len(window)
    if n == 0:
        return None
    mid = n // 2
    return window[mid] if n % 2 else 0.5 * (window[mid - 1] + window[mid])


def quartile_spread(values: Sequence[float]) -> Optional[float]:
    """The distance between the quartiles over the median: the spread
    the driver reads from a set of runs (linear interpolation between
    order statistics, as ``numpy.percentile`` does by default)."""
    window = sorted(values)
    n = len(window)
    if n < 2:
        return None

    def at(q):
        pos = q * (n - 1)
        lo = int(math.floor(pos))
        hi = min(lo + 1, n - 1)
        return window[lo] + (window[hi] - window[lo]) * (pos - lo)

    med = at(0.5)
    return (at(0.75) - at(0.25)) / med if med else None


def group_rates(times: Sequence[float], span: float) -> list:
    """Events a second in consecutive groups of the events at ``times``
    (ascending), each group the shortest that lasts at least ``span``
    seconds. A group runs from one event to another, so its length is
    measured and not a multiple of anything; the median over the groups
    is the steady rate, whatever single stalls the window held."""
    rates = []
    a = 0
    for b in range(1, len(times)):
        if times[b] - times[a] >= span:
            rates.append((b - a) / (times[b] - times[a]))
            a = b
    return rates


def merge_intervals(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip_intervals(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract_intervals(a, b):
    """The part of the disjoint sorted list ``a`` that no interval of
    the disjoint sorted list ``b`` covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out
