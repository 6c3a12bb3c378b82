"""Device time per call of the decode-step program (`_paged_decode_step`),
from the profiler's trace."""


def read(run):
    if run.trace is None:
        return None
    return run.trace.per_call_ms("_paged_decode_step")
