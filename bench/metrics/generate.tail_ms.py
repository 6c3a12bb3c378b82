"""Median over requests of `generate.tail`: the tokens stacked and copied
to the host after the last decode step, from the program's spans of a
traced run."""
from bench.readers import median
from bench.spans import requests, total


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return median(1e3 * total(inner, "generate.tail")
                  for _, inner in requests(spans))
