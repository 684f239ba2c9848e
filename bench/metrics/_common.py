"""Shared arithmetic of the metric readers: percentiles and the window's
token arrivals."""
from __future__ import annotations

import math


def percentile(values, q: float):
    """The q-th percentile by nearest rank (the smallest value with at
    least q% of the values at or below it); None for no values."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


def due_in_window(run):
    """Requests due in the window, primed streams excluded."""
    return [r for r in run.records
            if run.w0 <= r["due"] <= run.w1 and r["phase"] != "prime"]
