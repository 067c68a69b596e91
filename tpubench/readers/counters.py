"""Readers over the program's StatRegistry counts (core/monitor.py) and the
benchmark's own compile counts, as deltas between the snapshots taken at the
`open` and the `close` of the window."""
from __future__ import annotations


def delta(run, names, since="open", until="close", scale=1.0):
    """Sum of the deltas of every counter matching one of the glob patterns
    in `names`."""
    if since not in run.counters or until not in run.counters:
        return None
    return scale * sum(run.counter_delta(n, since, until) for n in names)


def total_ratio(run, num, den, scale=100.0):
    """Two counters' values at the end of the window, one over the other;
    None while the denominator is 0."""
    snap = run.counters.get("close")
    if not snap or not snap.get(den):
        return None
    return scale * snap.get(num, 0) / snap[den]

