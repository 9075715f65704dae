"""Arithmetic of the yardstick: percentiles and interval sets. No jax.

Intervals are ``(start, end)`` pairs on one clock; a set of them is a list
sorted by start with no overlaps (what :func:`union` returns).
"""

from __future__ import annotations

import math
import re
from typing import Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def spread(values: Sequence[float]) -> float:
    """Distance between the quartiles over the median: the driver's
    measure of how far runs of one cell disagree."""
    return (percentile(values, 75) - percentile(values, 25)) / median(values)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of interval set ``a`` that no interval of set ``b`` covers."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """Where nothing of ``busy`` runs inside [lo, hi]."""
    return subtract([(lo, hi)], busy)


def self_times(events: Sequence[Tuple[float, float]]) -> List[float]:
    """Self time of each ``(start, duration)`` event of ONE trace line, in
    the order given: its duration less what the events nested inside it
    cover. A line nests (a ``while`` holds the operations of its body), so
    the sum of self times is the line's busy time and no operation is
    counted twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    selfs = [d for _, d in events]
    stack: List[int] = []       # indices of open events, outermost first
    for i in order:
        s, d = events[i]
        while stack and s >= sum(events[stack[-1]]):
            stack.pop()
        if stack:
            selfs[stack[-1]] -= d
        stack.append(i)
    return [max(x, 0.0) for x in selfs]


def matcher(patterns: Sequence[str]):
    """Predicate over labels: true where any of the regular expressions is
    found. The patterns live in a metric's data file."""
    regs = [re.compile(p) for p in patterns]
    return lambda label: any(r.search(label) for r in regs)
