"""The statistics behind the metrics. Pure Python: no JAX, no clock.

Throughput end to end is window_rate(): all the work of the whole steps of the
window over all the time they took, host stalls included. The median over
equal-step-count segments leaves a one-off stall out; it is a per-layer
diagnostic, and stall_share() says how far the two lie apart.
"""
from __future__ import annotations

import math
import statistics

SEGMENTS = 5


def whole_steps(ends, t0, seconds):
    """The step ends that fall inside [t0, t0 + seconds], in order."""
    return [t for t in ends if t0 <= t <= t0 + seconds]


def window_rate(ends, t0, work):
    """All the work of the counted steps over the time they took: from t0 (the
    end of the last warm-up step) to the end of the last counted step, never
    the nominal window length. `work[i]` belongs to the step ending at
    `ends[i]`."""
    if not ends:
        return None
    return sum(work[:len(ends)]) / (ends[-1] - t0)


def segment_rates(ends, t0, work, segments=SEGMENTS):
    """Work per second of `segments` consecutive runs of equal step count.
    Steps left over at the end (fewer than `segments`) enter only
    window_rate()."""
    per = len(ends) // segments
    if per < 1:
        return []
    edges = [t0] + list(ends)
    return [sum(work[s * per:(s + 1) * per])
            / (edges[(s + 1) * per] - edges[s * per])
            for s in range(segments)]


def segment_median_rate(ends, t0, work, segments=SEGMENTS):
    rates = segment_rates(ends, t0, work, segments)
    return statistics.median(rates) if rates else None


def stall_share(ends, t0, work, segments=SEGMENTS):
    """1 - window rate over median-segment rate: the share of the window that
    the segment median leaves out. Near 0 on a steady run; one stall of 2 s in
    a 45 s window reads about 0.04."""
    med = segment_median_rate(ends, t0, work, segments)
    whole = window_rate(ends, t0, work)
    if not med or whole is None:
        return None
    return 1.0 - whole / med


def step_times(ends, t0):
    edges = [t0] + list(ends)
    return [b - a for a, b in zip(edges, edges[1:])]


def quantile(samples, q):
    """Linear-interpolated quantile, q in [0, 1]."""
    if not samples:
        return None
    xs = sorted(samples)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values):
    """The contract's spread: third minus first quartile of
    statistics.quantiles(values, n=4), as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
