"""Median over requests of JAX's backend compile inside the request's
`prefill` span (`jit.compile`: the compile cache's key and load, or a
compile), from the program's spans of a traced run."""
from bench.readers import median
from bench.spans import jit_in_prefill, requests, total


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return median(1e3 * total(jit_in_prefill(inner), "jit.compile")
                  for _, inner in requests(spans))
