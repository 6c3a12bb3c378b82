"""Median over requests of the host time before the first token that no
phase of the record covers: client TTFT less the client's queue and the
record's init, load, profile and prefill (routing, start_instance and the
prompt batch)."""
from bench.readers import median


def read(run):
    return median(1e3 * (s.ttft_s - s.queue_s - s.rec.init_s - s.rec.load_s
                         - s.rec.profile_s - s.rec.prefill_s)
                  for s in run.served)
