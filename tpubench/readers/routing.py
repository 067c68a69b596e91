"""Readers over the expert-routing counters a serving program keeps
(`serve/moe/*`, paddle_tpu/inference/serving/engine.py): ratios of two
counters' growth over the window. A program that routes nothing (or the
parent of the PR that added the counters) gives them nothing to read."""
from __future__ import annotations


def delta_ratio(run, num, den, scale=1.0):
    """The window's growth of counter `num` over that of `den`, times
    `scale`; None while `den` did not grow."""
    if "open" not in run.counters or "close" not in run.counters:
        return None
    d = run.counter_delta(den)
    return scale * run.counter_delta(num) / d if d else None
