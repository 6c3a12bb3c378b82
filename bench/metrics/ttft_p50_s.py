"""Median time to first token over the window's requests, from the moment
each was due (host clock)."""
from bench.readers import percentile


def read(run):
    xs = [s.ttft_s for s in run.served]
    return percentile(xs, 0.50) if xs else None
