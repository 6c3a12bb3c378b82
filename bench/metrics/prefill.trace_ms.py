"""Median over requests of the time JAX spent tracing and lowering inside
the request's `prefill` span (`jit.trace` + `jit.lower`, nested steps
counted once), from the program's spans of a traced run."""
from bench.readers import median
from bench.spans import jit_in_prefill, requests, total


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return median(1e3 * total(jit_in_prefill(inner), "jit.trace", "jit.lower")
                  for _, inner in requests(spans))
