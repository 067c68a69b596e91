"""Readers over the samples and scalars a run recorded itself."""
from __future__ import annotations

import statistics

from .. import estimators as est


def value(run, name, scale=1.0):
    v = run.values.get(name)
    return None if v is None else v * scale


def window_rate(run, ends="step_end_s", work="step_work"):
    """All the work of the window's whole steps over the time from the
    opening of the window to the end of the last of them."""
    e = run.samples.get(ends)
    if not e:
        return None
    return est.window_rate(e, 0.0, run.samples[work])


def segment_median_rate(run, ends="step_end_s", work="step_work"):
    """Median over five equal-step-count segments of work per second; see
    tpubench/estimators.py."""
    e = run.samples.get(ends)
    if not e:
        return None
    return est.segment_median_rate(e, 0.0, run.samples[work])


def stall_share(run, ends="step_end_s", work="step_work"):
    e = run.samples.get(ends)
    if not e:
        return None
    s = est.stall_share(e, 0.0, run.samples[work])
    return None if s is None else 100.0 * s


def step_max_over_median(run, ends="step_end_s"):
    e = run.samples.get(ends)
    if not e:
        return None
    dts = est.step_times(e, 0.0)
    return max(dts) / statistics.median(dts)


def quantile(run, sample, q):
    s = run.samples.get(sample)
    return est.quantile(s, q) if s else None


def mean(run, sample, scale=1.0):
    s = run.samples.get(sample)
    return statistics.fmean(s) * scale if s else None


def maximum(run, sample, scale=1.0):
    s = run.samples.get(sample)
    return max(s) * scale if s else None
