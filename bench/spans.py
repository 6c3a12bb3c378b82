"""Run one cell with the program's own tracer live, and read its spans.

  python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
      [--requests-out FILE] [--spans-out FILE]

A `bench/run.py --trace 1` run, plus what the harness does not do yet: the
engine and the fleet gateway get one `repro.obs.Tracer` whose spans also
enter the profiler trace (`jax.profiler.TraceAnnotation`), JAX's compile
steps are spans for the window (`repro.obs.jit`), set-up's spans are
dropped at the window's opening, and the trace's idle gaps are put down to
the program's spans too (the innermost span wins, so a gap under
`prefill.dispatch` or `jit.*` is named there and not under the harness's
`prefill`).  The readers of `METRICS` read the window's spans as
`run.spans`; the result line carries their values beside the harness's
metrics.  `--spans-out` writes one JSON line per request of its summed
spans (milliseconds).

A request's spans are those its serving thread closed inside its `serve`
span (the rule `repro.obs` states); the grouping here is the benchmark's
own, so the program cannot move it.
"""
from __future__ import annotations

import contextlib
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

#: Per-layer metrics read from the program's spans (`bench/metrics/`).
METRICS = ("prefill.trace_ms", "prefill.compile_ms", "jit.traces_per_req",
           "gateway.setup_ms", "generate.tail_ms")
#: The program's spans the trace's idle gaps may be put down to.
PROGRAM_SPANS = ("serve", "route", "start_instance", "make_prefill_batch",
                 "prefill", "prefill.dispatch", "decode", "generate.tail",
                 "load", "profile", "jit.trace", "jit.lower", "jit.compile")
JIT = ("jit.trace", "jit.lower", "jit.compile")


# ------------------------------------------------------------ the reading
def requests(spans) -> list[tuple[object, list]]:
    """(serve span, the spans its thread closed inside it) per request, in
    the order the requests were served."""
    serves = sorted((s for s in spans if s.name == "serve"),
                    key=lambda s: s.begin)
    out = []
    for s in serves:
        inner = [e for e in spans if e is not s and e.end is not None
                 and e.thread == s.thread and s.begin <= e.begin
                 and e.end <= s.end]
        out.append((s, inner))
    return out


def within(spans, outer) -> list:
    return [e for e in spans if outer.begin <= e.begin and e.end <= outer.end]


def outermost(spans) -> list:
    """The spans no other of `spans` contains: nested JAX steps (a trace
    inside a trace) are counted once, in their parent."""
    out = []
    for e in sorted(spans, key=lambda e: (e.begin, -e.end)):
        if not out or e.end > out[-1].end:
            out.append(e)
    return out


def total(spans, *names) -> float:
    return sum(e.end - e.begin for e in spans if e.name in names)


def jit_in_prefill(inner) -> list:
    """The outermost `jit.*` spans inside the request's `prefill` span."""
    pre = [e for e in inner if e.name == "prefill"]
    jit = [e for e in inner if e.name in JIT]
    return outermost([j for p in pre for j in within(jit, p)])


def summed(inner) -> dict:
    """One request's span sums in milliseconds."""
    jit = jit_in_prefill(inner)
    row = {n: 1e3 * total(inner, n)
           for n in ("route", "start_instance", "make_prefill_batch",
                     "load", "prefill", "prefill.dispatch", "decode",
                     "generate.tail")}
    for n in JIT:
        row[n] = 1e3 * total(jit, n)
    row["prefill.device_wait"] = row["prefill"] - row["prefill.dispatch"]
    row["jit.cache_hits"] = sum(1 for e in jit if e.name == "jit.compile"
                                and (e.args or {}).get("cache_hit"))
    return row


# ------------------------------------------------------------ the run
@contextlib.contextmanager
def program_idle():
    """While open, the trace reduction puts idle gaps down to the
    program's spans as well as the harness's."""
    from bench import xplane

    layer_spans = xplane.LAYER_SPANS
    xplane.LAYER_SPANS = layer_spans + PROGRAM_SPANS
    try:
        yield
    finally:
        xplane.LAYER_SPANS = layer_spans


@contextlib.contextmanager
def program_tracer(tracer):
    """While open, the harness's server builds its engine and gateway with
    `tracer`, and its window installs the JAX compile spans after dropping
    set-up's events."""
    from bench import harness
    from repro.launch import serve
    from repro.obs import jit as obs_jit
    from repro.serving import engine as E

    Engine, fleet_gateway = E.Engine, serve.fleet_gateway
    window = harness.window

    class TracedEngine(Engine):
        def __init__(self, *a, **kw):
            super().__init__(*a, tracer=tracer, **kw)

    def traced_window(*a, **kw):
        tracer.clear()
        uninstall = obs_jit.install(tracer)
        try:
            return window(*a, **kw)
        finally:
            uninstall()

    E.Engine = TracedEngine
    serve.fleet_gateway = (
        lambda args, engines, _=None: fleet_gateway(args, engines, tracer))
    harness.window = traced_window
    try:
        yield
    finally:
        E.Engine, serve.fleet_gateway = Engine, fleet_gateway
        harness.window = window


def main(argv=None, t_start=None) -> int:
    import argparse

    import jax

    from bench import harness, spec
    from repro.obs import Tracer

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--requests-out", default=None)
    ap.add_argument("--spans-out", default=None)
    a = ap.parse_args(argv)
    try:
        cell = spec.load_cell(a.workload)
        devices = harness.chips(cell.chips)
    except (harness.NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    tracer = Tracer(annotate=jax.profiler.TraceAnnotation,
                    max_events=1 << 20)
    with program_tracer(tracer), program_idle():
        out = harness.measure(cell, a.seed, a.seconds, True, t_start,
                              devices=devices, requests_out=a.requests_out)
    spans = [e for e in tracer.events() if e.end is not None]
    run = SimpleNamespace(spans=spans)
    for name in METRICS:
        v = spec._reader(spec.ROOT, name)(run)
        if v is not None:
            out["metrics"][name] = {"value": v, "unit": _unit(name)}
    rows = [{"rid": (s.args or {}).get("rid"),
             "cold": (s.args or {}).get("cold"),
             "serve": 1e3 * (s.end - s.begin), **summed(inner)}
            for s, inner in requests(spans)]
    if a.spans_out:
        with open(a.spans_out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    slow = [r for r in rows if r["prefill"] > 1e3]
    print(f"spans: {len(spans)} in the window, {tracer.dropped_events} "
          f"dropped; {len(rows)} requests; prefill over 1 s: "
          f"{json.dumps(slow)}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


def _unit(name: str) -> str:
    return "count" if name == "jit.traces_per_req" else "ms"


if __name__ == "__main__":
    T_START = time.perf_counter()
    ROOT = Path(__file__).resolve().parents[1]
    # the script's own directory would shadow modules by its file names
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    sys.exit(main(t_start=T_START))
