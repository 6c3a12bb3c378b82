"""JAX's compile pipeline as spans of the program's tracer.

JAX times each step of turning a Python function into a device program and
reports it through ``jax.monitoring``: tracing to a jaxpr, lowering the
jaxpr to an MLIR module, and the backend compile (which, with the
persistent compile cache on, is the cache key and the load of a cached
executable).  ``install(tracer)`` turns those reports into spans on track
``jit``:

  ``jit.trace``    /jax/core/compile/jaxpr_trace_duration
  ``jit.lower``    /jax/core/compile/jaxpr_to_mlir_module_duration
  ``jit.compile``  /jax/core/compile/backend_compile_duration

each with ``args={"fun": <function name>}``; ``jit.compile`` also carries
``"cache_hit"``, true when the persistent cache served the executable
(``/jax/compilation_cache/cache_hits`` fired inside it).  A span lies
inside the program span that was open when JAX did the work, so it says
which step recompiled, and how long that took.

JAX stamps these intervals with ``time.time()``; they are moved to the
tracer's clock by an offset read once at install.  Install only when
tracing is on: with no listener registered JAX pays nothing.  jax is
imported on install, so ``repro.obs`` stays importable without it.
"""
from __future__ import annotations

import threading
import time
from typing import Callable

TRACK = "jit"
SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}
CACHE_HIT = "/jax/compilation_cache/cache_hits"


def install(tracer) -> Callable[[], None]:
    """Emit JAX's compile-pipeline intervals as spans of `tracer` until
    the returned function is called."""
    import jax.monitoring as mon

    offset = tracer.clock() - time.time()
    hit = threading.local()  # a cache hit inside this thread's compile

    def on_span(event, start, end, **kw):
        name = SPANS.get(event)
        if name is None:
            return
        args = {"fun": kw.get("fun_name", "")}
        if name == "jit.compile":
            args["cache_hit"] = getattr(hit, "seen", False)
            hit.seen = False
        tracer.emit(name, start + offset, end + offset, track=TRACK,
                    cat="jit", args=args, thread=threading.get_ident())

    def on_event(event, **kw):
        if event == CACHE_HIT:
            hit.seen = True

    mon.register_event_time_span_listener(on_span)
    mon.register_event_listener(on_event)

    def uninstall():
        mon.unregister_event_time_span_listener(on_span)
        mon.unregister_event_listener(on_event)

    return uninstall
