"""Interval arithmetic on ``(t0, t1)`` pairs: union, intersection, clip.

The anti-sum-of-averages primitives every reader of span records shares
(``telemetry/trace.py``: self time, the device's timeline, a step's
report; ``telemetry/devstats.scope_seconds``). Pure, and a leaf: this
module imports nothing of the package. Tests hold it to brute-force
oracles (``tests/test_profiler.py``).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def union_intervals(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge ``(t0, t1)`` intervals into a disjoint sorted union."""
    ivs = sorted((float(a), float(b)) for a, b in intervals if b > a)
    out: List[Interval] = []
    for a, b in ivs:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def union_length(intervals: Sequence[Interval]) -> float:
    """Total length of the union: two spans covering the same
    wall-clock second count it once."""
    return sum(b - a for a, b in union_intervals(intervals))


def intersect_disjoint(span: Interval, merged: Sequence[Interval]) -> float:
    """``|span ∩ merged|`` for an ALREADY disjoint sorted union (a caller
    that intersects one union against many spans merges it once)."""
    a0, b0 = span
    total = 0.0
    for a, b in merged:
        lo, hi = max(a, a0), min(b, b0)
        if hi > lo:
            total += hi - lo
    return total


def intersect_length(span: Interval, intervals: Sequence[Interval]) -> float:
    """``|span ∩ union(intervals)|``: the overlap-credit primitive."""
    return intersect_disjoint(span, union_intervals(intervals))


def clip(t0: float, t1: float, lo: float, hi: float) -> Optional[Interval]:
    """``[t0, t1] ∩ [lo, hi]``, or ``None`` where that is empty."""
    a, b = max(t0, lo), min(t1, hi)
    return (a, b) if b > a else None
