"""Shared arithmetic of the metric readers: percentiles, the window's
token gaps and the requests due in it."""
from __future__ import annotations

import math


def percentile(values, q: float):
    """The q-th percentile by nearest rank (the smallest value with at
    least q% of the values at or below it); None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def window_gaps(run) -> list:
    """Every gap, in ms, between consecutive streamed tokens of one
    request, over all requests, where both tokens reached the client
    inside the window."""
    gaps = []
    for r in run.records:
        t = [x for x in r["times"] if run.w0 <= x <= run.w1]
        gaps += [(b - a) * 1e3 for a, b in zip(t, t[1:])]
    return gaps


def due_in_window(run):
    """Requests due in the window, primed streams excluded."""
    return [r for r in run.records
            if run.w0 <= r["due"] <= run.w1 and r["phase"] != "prime"]
