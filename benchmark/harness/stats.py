"""Percentile and median arithmetic of the benchmark.

Kept here, under the benchmark's own path, so that no later change to
the program can move the yardstick (the program's ``profiler.StepTimer``
and ``LatencyStats`` do the same arithmetic for its own reports).
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule). ``math.inf`` samples — a
    failed or refused request "misses any limit" — sort last, and a
    percentile that lands on one is ``inf``. None for no samples."""
    xs: List[float] = sorted(values)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside [0, 100]")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = math.ceil(rank)
    if math.isinf(xs[hi]):
        return xs[hi]
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median — the run-to-run
    spread the bounds in ``BENCHMARK.json`` are set from."""
    if len(values) < 2:
        return None
    mid = median(values)
    if not mid:
        return None
    return (percentile(values, 75.0) - percentile(values, 25.0)) / abs(mid)


def sustained_rate(marks: Sequence[float], amounts: Sequence[float],
                   stretch_s: float) -> Optional[float]:
    """The median rate over every stretch of a window that a mark opens.

    ``marks`` are the increasing times at which work was handed over
    (the window's start, then the end of each tick) and ``amounts[i]``
    is what was handed over at ``marks[i + 1]``. From every mark a
    stretch runs to the first mark at least ``stretch_s`` later; its
    rate is the amount inside over its length, and the result is the
    median of those rates. Stretches begin and end on marks because work
    arrives in waves at the marks: a stretch cut by the clock would hold
    a wave more or less by chance. A pause that falls into fewer than
    half the stretches (one shorter than about the window less twice
    ``stretch_s``) does not move the result, whereas a lasting change of
    rate moves it in full. The whole window's rate where no stretch
    fits; None without work."""
    n = len(amounts)
    if n == 0 or len(marks) != n + 1:
        return None
    total = [0.0]
    for a in amounts:
        total.append(total[-1] + a)
    rates: List[float] = []
    j = 0
    for i in range(n):
        j = max(j, i + 1)
        while j <= n and marks[j] - marks[i] < stretch_s:
            j += 1
        if j > n:
            break
        rates.append((total[j] - total[i]) / (marks[j] - marks[i]))
    if not rates:
        return total[n] / (marks[n] - marks[0])
    return median(rates)


def union_seconds(intervals: Iterable[Sequence[float]]) -> float:
    """Total length covered by ``(start, end)`` intervals, overlaps
    counted once."""
    total = 0.0
    end = -math.inf
    for a, b in sorted((i[0], i[1]) for i in intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def subtract_cover(intervals: Iterable[Sequence[float]],
                   cover: Iterable[Sequence[float]]) -> float:
    """Length of ``intervals`` (as a union) NOT covered by ``cover`` —
    a span's self time, a collective's exposed part."""
    own = merge(intervals)
    cov = merge(cover)
    total = 0.0
    j = 0
    for a, b in own:
        cur = a
        while j < len(cov) and cov[j][1] <= cur:
            j += 1
        k = j
        while k < len(cov) and cov[k][0] < b:
            if cov[k][0] > cur:
                total += cov[k][0] - cur
            cur = max(cur, cov[k][1])
            k += 1
        if cur < b:
            total += b - cur
    return total


def merge(intervals):
    out: List[List[float]] = []
    for a, b in sorted((i[0], i[1]) for i in intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        elif b > a:
            out.append([a, b])
    return out
