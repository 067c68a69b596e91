"""The statistics behind the metrics, on synthetic samples: the end-to-end
rate is over all the work and all the time, the segment median is the
diagnostic beside it."""
import statistics

import pytest

from tpubench import estimators as est

STEP = 0.4
TOKENS = 16384


def _ends(n, stalls=()):
    """End times of n steps of STEP seconds; stalls = {step: extra seconds}."""
    stalls = dict(stalls)
    t, out = 0.0, []
    for i in range(n):
        t += STEP + stalls.get(i, 0.0)
        out.append(t)
    return out


def test_whole_steps_only():
    ends = _ends(30)
    counted = est.whole_steps(ends, 0.0, 10.1)
    assert len(counted) == 25             # the 26th ends at 10.4 s
    work = [TOKENS] * len(counted)
    # over the elapsed time of the counted steps, not the nominal 10.1 s
    assert est.window_rate(counted, 0.0, work) == pytest.approx(TOKENS / STEP)
    assert 25 * TOKENS / 10.1 < TOKENS / STEP


def test_segments_have_equal_step_counts_and_drop_the_remainder():
    ends = _ends(23)
    rates = est.segment_rates(ends, 0.0, [TOKENS] * 23)
    assert len(rates) == 5
    assert all(r == pytest.approx(TOKENS / STEP) for r in rates)
    assert est.segment_rates(_ends(4), 0.0, [TOKENS] * 4) == []
    assert est.segment_median_rate(_ends(4), 0.0, [TOKENS] * 4) is None


@pytest.mark.parametrize("stalls,window_moves,median_moves", [
    ({}, False, False),
    ({37: 2.0}, True, False),                       # one host stall
    ({i: 0.1 for i in range(0, 100, 5)}, True, True),   # a slow loader
], ids=["steady", "single-stall", "recurring-stall"])
def test_a_single_stall_moves_the_window_figure_not_the_median(
        stalls, window_moves, median_moves):
    ends = _ends(100, stalls)
    work = [TOKENS] * 100
    steady = TOKENS / STEP
    whole = est.window_rate(ends, 0.0, work)
    med = est.segment_median_rate(ends, 0.0, work)
    assert (whole < 0.99 * steady) == window_moves
    assert (med < 0.99 * steady) == median_moves
    share = est.stall_share(ends, 0.0, work)
    if window_moves and not median_moves:
        assert share == pytest.approx(2.0 / (100 * STEP + 2.0), rel=1e-6)
    if not window_moves:
        assert share == pytest.approx(0.0, abs=1e-9)


def test_uneven_work_per_step():
    # an engine step emits what its batch holds
    ends = _ends(10)
    work = [32, 32, 32, 32, 30, 30, 30, 30, 28, 28]
    rates = est.segment_rates(ends, 0.0, work)
    assert rates == pytest.approx([64 / .8, 64 / .8, 60 / .8, 60 / .8,
                                   56 / .8])
    assert est.segment_median_rate(ends, 0.0, work) == pytest.approx(75.0)


class _Run:
    """What a reader needs of a run."""

    def __init__(self, ends, work):
        self.samples = {"step_end_s": ends, "step_work": work}


@pytest.mark.parametrize("metric", ["train_tok_s_chip", "serve_tok_s"])
def test_the_end_to_end_rate_is_all_the_work_over_all_the_time(metric):
    """A stall inside the window, however short-lived, lowers the end-to-end
    value by exactly the time it took; only the per-layer segment median
    leaves it out."""
    import json
    import os

    from tpubench import core

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "tpubench", "metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    work = [TOKENS] * 50
    steady = core.read_metric(_Run(_ends(50), work), spec)
    stalled = core.read_metric(_Run(_ends(50, {7: 1.0}), work), spec)
    assert steady == pytest.approx(TOKENS / STEP)
    assert stalled == pytest.approx(50 * TOKENS / (50 * STEP + 1.0))
    assert core.read_metric(_Run([], []), spec) is None


def test_quantile_and_spread():
    xs = list(range(1, 12))
    assert est.quantile(xs, 0.5) == 6
    assert est.quantile(xs, 0.9) == pytest.approx(10.0)
    assert est.quantile([], 0.5) is None
    vals = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert est.iqr_share(vals) == pytest.approx((q3 - q1) / 102.5)
