"""Readers over the benchmark's own host spans."""
from __future__ import annotations


def share(run, span, scale=100.0):
    """Time inside spans of this name that lies in the window, over the
    window."""
    if not run.window or run.window[1] <= run.window[0]:
        return None
    lo, hi = run.window
    inside = sum(max(0.0, min(b, hi) - max(a, lo))
                 for name, a, b in run.spans if name == span)
    return scale * inside / (hi - lo)
