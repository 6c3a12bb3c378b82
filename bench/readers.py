"""Helpers the metric readers share."""
from __future__ import annotations

import statistics


def percentile(xs, q: float) -> float:
    """Sorted values, index min(n - 1, int(n * q)): the convention of
    `repro.obs.metrics.percentile`, copied so that the program cannot move
    it."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def median(xs):
    xs = list(xs)
    return statistics.median(xs) if xs else None


def idle_pct(run):
    """Share of the traced window in which no operation ran on the device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
