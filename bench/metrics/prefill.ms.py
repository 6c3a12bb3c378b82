"""Median prefill wall of the window's requests (the record's prefill_s,
which ends on the device)."""
from bench.readers import median


def read(run):
    return median(1e3 * s.rec.prefill_s for s in run.served)
