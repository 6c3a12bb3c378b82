"""Median over requests of the gateway's host steps before prefill:
`route` + `start_instance` + `make_prefill_batch`, from the program's
spans of a traced run."""
from bench.readers import median
from bench.spans import requests, total


def read(run):
    spans = getattr(run, "spans", None)
    if not spans:
        return None
    return median(1e3 * total(inner, "route", "start_instance",
                              "make_prefill_batch")
                  for _, inner in requests(spans))
