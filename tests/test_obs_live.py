"""The observability plane on the real data plane: JAX's compile steps as
spans (`repro.obs.jit`), the span family of a served request through the
real-plane `FleetGateway`, and the pin that tracing off costs the served
path no listener and no profiler annotation."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs import all_configs
from repro.core.trace import Request
from repro.obs import Tracer
from repro.obs import jit as obs_jit
from repro.serverless import FleetGateway
from repro.serving.engine import Engine


def _fresh(k):
    """A jitted function JAX has not seen: its own closure constant."""
    return jax.jit(lambda x: jnp.sin(x) * k + k)


def test_jit_spans_nest_in_the_open_span_with_the_function_name():
    x = jnp.ones(8)
    tracer = Tracer()
    uninstall = obs_jit.install(tracer)
    try:
        with tracer.span("prefill.dispatch"):
            _fresh(3.0)(x).block_until_ready()
    finally:
        uninstall()
    outer = next(e for e in tracer.events() if e.name == "prefill.dispatch")
    jit = [e for e in tracer.events() if e.track == "jit"]
    assert {"jit.trace", "jit.lower", "jit.compile"} <= {e.name for e in jit}
    for e in jit:
        # moved onto the tracer's clock: inside the span that was open
        assert outer.begin <= e.begin <= e.end <= outer.end, e
        assert e.thread == threading.get_ident()
        assert e.args["fun"]
    assert any("lambda" in e.args["fun"] for e in jit)
    assert all(isinstance(e.args["cache_hit"], bool) for e in jit
               if e.name == "jit.compile")
    # uninstalled: a fresh function adds nothing
    n = len(tracer.events())
    _fresh(4.0)(x).block_until_ready()
    assert len(tracer.events()) == n


def test_jit_compile_marks_a_cache_hit_inside_it():
    """The persistent cache reports a hit as an event inside the compile's
    interval; only that compile carries `cache_hit`.  Events are replayed
    through `jax.monitoring` by hand, with time.time() stamps."""
    import jax.monitoring as mon

    tracer = Tracer()
    uninstall = obs_jit.install(tracer)
    try:
        t = time.time()
        mon.record_event(obs_jit.CACHE_HIT)
        mon.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", t, t + 0.5,
            fun_name="jit(f)")
        mon.record_event_time_span(
            "/jax/core/compile/backend_compile_duration", t + 1, t + 1.25,
            fun_name="jit(g)")
        mon.record_event_time_span("/jax/some/other_duration", t, t + 1)
    finally:
        uninstall()
    hit, miss = tracer.events()
    assert (hit.args, miss.args) == ({"fun": "jit(f)", "cache_hit": True},
                                     {"fun": "jit(g)", "cache_hit": False})
    assert hit.end - hit.begin == pytest.approx(0.5)
    # time.time() moved onto perf_counter: the interval is near now
    assert abs(hit.begin - time.perf_counter()) < 60


def _small_cfg():
    cfg = all_configs()["llama3.2-1b"].smoke()
    return dataclasses.replace(cfg, num_layers=2, vocab_size=512)


def _serve(tracer, n=2):
    eng = Engine(256 * 1024 * 1024, tracer=tracer)
    eng.register("m", _small_cfg())
    gw = FleetGateway([eng], keep_alive="zero", prompt_len=8, gen_tokens=2,
                      tracer=tracer)
    gw.run_trace([Request(time=10.0 * i, model_id="m", dataset="t",
                          prompt_tokens=8, output_tokens=2, batch_size=1)
                  for i in range(n)])
    eng.close()
    return gw.sink.records


def test_fleet_serve_span_family_per_request():
    tracer = Tracer()
    records = _serve(tracer)
    events = tracer.events()
    serves = [e for e in events if e.name == "serve"]
    assert len(serves) == len(records) == 2
    for rid, (s, rec) in enumerate(zip(serves, records)):
        assert s.args == {"rid": rid, "model": "m", "cold": True}
        mine = {e.name: e for e in events
                if e is not s and e.thread == s.thread
                and s.begin <= e.begin and e.end <= s.end}
        for name in ("route", "load", "start_instance", "make_prefill_batch",
                     "prefill", "prefill.dispatch", "decode",
                     "generate.tail"):
            assert name in mine, f"request {rid}: no {name} span"
        pre, disp = mine["prefill"], mine["prefill.dispatch"]
        assert pre.begin <= disp.begin and disp.end <= pre.end
        # in order: route, then the serve's steps, prefill before decode
        assert mine["route"].end <= mine["load"].begin
        assert mine["make_prefill_batch"].end <= pre.begin
        assert pre.end <= mine["decode"].begin
        assert mine["decode"].end <= mine["generate.tail"].begin
        # the record's walls are the spans' intervals
        assert rec.prefill_s == pytest.approx(pre.end - pre.begin, abs=1e-3)
        assert rec.decode_s == pytest.approx(
            mine["decode"].end - mine["decode"].begin, abs=1e-3)
        # first token: from the serve's start, within it, after prefill's
        # own wall
        assert rec.prefill_s < rec.first_token_s <= s.end - s.begin
    # the trace-clock request families are still there, on their tracks
    assert {e.track for e in events if e.name == "request"} == {"req:0",
                                                                "req:1"}


def test_cold_starts_trace_the_prefill_program_once_per_prompt_shape():
    """Keep-alive zero: each request starts a new instance (and model).  The
    prefill program is traced in the first request's `prefill` span only,
    and a new prompt length traces it exactly once more."""
    # a vocabulary no other test compiles, so the first request traces
    cfg = dataclasses.replace(_small_cfg(), vocab_size=320)
    tracer = Tracer()
    eng = Engine(256 * 1024 * 1024, tracer=tracer)
    eng.register("m", cfg)
    gw = FleetGateway([eng], keep_alive="zero", prompt_len=8, gen_tokens=2,
                      tracer=tracer)
    uninstall = obs_jit.install(tracer)
    try:
        for t, prompt_len in ((0, 8), (10, 8), (20, 8), (30, 12)):
            gw.prompt_len = prompt_len
            gw.run_trace([Request(time=float(t), model_id="m", dataset="t",
                                  prompt_tokens=prompt_len, output_tokens=2,
                                  batch_size=1)])
    finally:
        uninstall()
        eng.close()
    events = tracer.events()
    serves = sorted((e for e in events if e.name == "serve"),
                    key=lambda e: e.begin)
    assert [s.args["cold"] for s in serves] == [True] * 4
    per_request = []
    for s in serves:
        pre = next(e for e in events if e.name == "prefill"
                   and e.thread == s.thread and s.begin <= e.begin
                   and e.end <= s.end)
        per_request.append([e.args["fun"] for e in events
                            if e.name == "jit.trace"
                            and pre.begin <= e.begin and e.end <= pre.end])
    assert [sum("_prefill_forward" in f for f in funs)
            for funs in per_request] == [1, 0, 0, 1]
    # a warm shape traces nothing at all in prefill, not just the forward
    assert per_request[1] == per_request[2] == []


def test_tracing_off_enters_no_annotation_and_registers_no_listener(
        monkeypatch):
    import jax.monitoring as mon

    calls = []

    def refuse(name):
        def fn(*a, **kw):
            calls.append(name)
        return fn

    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        refuse("TraceAnnotation"))
    for name in ("register_event_listener",
                 "register_event_duration_secs_listener",
                 "register_event_time_span_listener",
                 "register_scalar_listener"):
        monkeypatch.setattr(mon, name, refuse(name))
    records = _serve(None)
    assert len(records) == 2 and all(r.tokens for r in records)
    assert records[0].first_token_s > 0
    assert calls == []
