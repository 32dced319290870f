"""Percentile and rate arithmetic of the benchmark.

`percentile` is the pick rule of `tools/rados_bench.py:percentiles()`
(sort, take element `min(n - 1, int(q * n))`), copied here so that no
later PR to the program can change what a tail means.
"""

from __future__ import annotations


def percentile(values, q: float) -> float | None:
    """The q-quantile (0 <= q < 1) of `values` by the nearest-rank rule
    above; None for an empty sample."""
    a = sorted(values)
    if not a:
        return None
    return float(a[min(len(a) - 1, int(q * len(a)))])


def rate(total: float, seconds: float) -> float | None:
    """Work over wall time; None when no time passed."""
    if seconds <= 0:
        return None
    return total / seconds


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals, overlaps counted
    once."""
    covered, edge = 0.0, None
    for start, end in sorted(intervals):
        if edge is None or start > edge:
            covered += end - start
            edge = end
        elif end > edge:
            covered += end - edge
            edge = end
    return covered


def bandwidth_roofline_pct(work_bytes: float, peak_bytes_per_s: float,
                           busy_s: float) -> float | None:
    """The least time the chip could take to move `work_bytes` through
    its memory, as a share of the time it was busy. None where there is
    nothing to read: never 0."""
    if not work_bytes or busy_s <= 0:
        return None
    return 100.0 * (work_bytes / peak_bytes_per_s) / busy_s
