"""Observability plane (DESIGN.md §18): span tracing, metrics, export.

One instrumentation surface for BOTH planes.  The tracer is clock-injected
— the real data plane stamps spans with ``time.perf_counter`` walls, the
modeled/sim plane passes explicit virtual trace-clock timestamps — so a
request's phase timeline has one vocabulary everywhere, and the
span-accounting identity (Σ child phase spans == reported TTFT, unattributed
time ≈ 0) can be asserted on any run.

On the real plane the tracer is also what the profiler shows: built with
``annotate=jax.profiler.TraceAnnotation`` it opens an annotation around
every live span, and ``repro.obs.jit.install`` adds JAX's own compile
steps (``jit.trace``, ``jit.lower``, ``jit.compile``) as spans on track
``jit``.  The served path's spans, for one request:

  ``serve`` (args ``rid``, ``model``, ``cold``)
    ``route``                       routing and room on the engine
    ``load`` ⊃ ``init``, ``store.read``, ``h2d``, ``profile``, ...
    ``start_instance``, ``make_prefill_batch``
    ``prefill`` ⊃ ``prefill.dispatch`` ⊃ ``jit.*``   ends on the device
    ``decode``                      ends with the last token on the device
    ``generate.tail``               tokens stacked and copied to the host

**A request's spans are the live spans that its serving thread closed
inside its ``serve`` span** (same ``SpanEvent.thread``, begin and end
within the ``serve`` span's).  Spans of other threads, such as the
prefetch worker's ``prefetch.promote``, and the trace-clock ``req:*``
families that ``trace_request`` emits, are not the request's.

Deliberately imports nothing from the rest of the package except
``repro.stats`` (which itself imports nothing): every layer — core, serving,
serverless, benchmarks — may import this one without cycles.
"""
from repro.obs.accounting import (cost_model_ratios, obs_stats,
                                  request_accounting, trace_request)
from repro.obs.export import chrome_trace, write_chrome_trace
from repro.obs.metrics import Counter, MetricsRegistry, percentile
from repro.obs.ring import BoundedLog
from repro.obs.tracer import (NULL_TRACER, FlightRecorder, SpanEvent,
                              Tracer)

__all__ = [
    "BoundedLog",
    "Counter",
    "FlightRecorder",
    "MetricsRegistry",
    "NULL_TRACER",
    "SpanEvent",
    "Tracer",
    "chrome_trace",
    "cost_model_ratios",
    "obs_stats",
    "percentile",
    "request_accounting",
    "trace_request",
    "write_chrome_trace",
]
