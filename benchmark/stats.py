"""Percentile and spread arithmetic of the benchmark (kept here so that no
later PR can change how a tail or a bound is computed)."""

import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) of `values` by linear interpolation
    between closest ranks (numpy's default "linear" method): rank
    q/100 * (n - 1) in the sorted sample.  Raises on an empty sample: a
    metric with nothing to read is left out, not reported as 0."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError("q=%r outside 0..100" % (q,))
    rank = q / 100.0 * (len(xs) - 1)
    lo = int(math.floor(rank))
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def median(values):
    return percentile(values, 50.0)


def iqr_spread(values):
    """Distance between the first and third quartile as a share of the
    median, with the quartiles of `statistics.quantiles(values, n=4)` — the
    spread the bounds in BENCHMARK.json are set from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)


def union_seconds(intervals):
    """Total length covered by a list of (start, end) intervals."""
    return sum(e - s for s, e in merge_intervals(intervals))


def merge_intervals(intervals):
    """Sorted disjoint union of (start, end) intervals."""
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


def gaps(merged, start, end):
    """The holes a merged interval list leaves in [start, end]."""
    out, at = [], start
    for s, e in merged:
        if e <= start or s >= end:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < end:
        out.append((at, end))
    return out
