"""Arithmetic shared by the runners and the metric readers: percentiles and
unions of time intervals."""

from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank: the smallest value with at least
    q% of the values at or below it.  `inf` entries (refused or lost
    requests) sort last, so they count as beyond every limit."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged, start: float, end: float) -> float:
    """Length of [start, end] covered by disjoint sorted intervals."""
    tot = 0.0
    for s, e in merged:
        if e <= start:
            continue
        if s >= end:
            break
        tot += min(e, end) - max(s, start)
    return tot


def gaps(merged, start: float, end: float) -> list[tuple[float, float]]:
    """The parts of [start, end] that disjoint sorted intervals leave free."""
    out = []
    t = start
    for s, e in merged:
        if e <= t:
            continue
        if s >= end:
            break
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < end:
        out.append((t, end))
    return out


def idle_share(busy, windows) -> float:
    """1 - busy time inside the windows over the windows' length, in %."""
    busy = union(busy)
    windows = union(windows)
    total = sum(e - s for s, e in windows)
    if total <= 0:
        raise ValueError("idle share over an empty window")
    inside = sum(covered(busy, s, e) for s, e in windows)
    return 100.0 * (1.0 - inside / total)
