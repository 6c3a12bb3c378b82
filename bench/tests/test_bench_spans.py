"""The readers of the program's spans, on spans planted by hand where
every number is known; the idle reduction that puts a gap down to a
program span inside the harness's `prefill`; and a run of the tiny cell
with the program's tracer live."""
import time
from types import SimpleNamespace

import jax
import pytest
from jax.profiler import ProfileData

from bench import harness, spans, spec, xplane
from bench.tests.test_bench_xplane import _events
from bench.tests.tiny import tiny_checkout
from repro.obs import SpanEvent

T = 7  # the serving thread
W = 9  # a worker thread


def _span(name, begin, end, thread=T, **args):
    return SpanEvent(name, "serve", begin, end, "phase", args or None,
                     thread)


def _request(t0, *, route, dispatch, jit, wait, tail):
    """One request starting at `t0` (seconds): route, start_instance 1 ms,
    make_prefill_batch 2 ms, prefill (dispatch with the `jit` steps
    inside, then `wait` on the device), decode 10 ms, then the tail."""
    out, t = [], t0
    out.append(_span("route", t, t + route))
    t += route
    out.append(_span("start_instance", t, t + 0.001))
    out.append(_span("make_prefill_batch", t + 0.001, t + 0.003))
    t += 0.003
    p0 = t
    j = t
    for name, d in jit:
        out.append(_span(name, j, j + d))
        j += d
    t += dispatch
    out.append(_span("prefill.dispatch", p0, t))
    t += wait
    out.append(_span("prefill", p0, t))
    out.append(_span("decode", t, t + 0.010))
    t += 0.010
    out.append(_span("generate.tail", t, t + tail))
    t += tail
    out.insert(0, _span("serve", t0, t, rid=0))
    return out


def _planted():
    a = _request(0.0, route=0.002, dispatch=0.100, wait=0.004, tail=0.020,
                 jit=[("jit.trace", 0.010), ("jit.lower", 0.030),
                      ("jit.compile", 0.040)])
    # a nested trace counts once, in its parent
    a.append(_span("jit.trace", 0.0060, 0.0070))
    b = _request(1.0, route=0.004, dispatch=0.200, wait=0.004, tail=0.040,
                 jit=[("jit.trace", 0.020), ("jit.lower", 0.050),
                      ("jit.compile", 0.080)])
    c = _request(2.0, route=0.006, dispatch=0.300, wait=0.004, tail=0.060,
                 jit=[("jit.trace", 0.030), ("jit.lower", 0.070),
                      ("jit.compile", 0.100)])
    noise = [
        # another thread's span inside a request is not the request's
        _span("generate.tail", 1.0, 1.1, thread=W),
        # nor a trace-clock span of the request families
        _span("route", 1.0, 1.1, thread=None),
        # a compile outside every prefill
        _span("jit.compile", 3.0, 3.5),
    ]
    return a + b + c + noise


@pytest.mark.parametrize("name, value", [
    ("prefill.trace_ms", 20.0 + 50.0),  # median of 40, 70, 100
    ("prefill.compile_ms", 80.0),
    ("jit.traces_per_req", 4 / 3),  # the nested one counts as a trace
    ("gateway.setup_ms", 4.0 + 3.0),  # route + 1 + 2 ms
    ("generate.tail_ms", 40.0),
])
def test_reader_on_planted_spans(name, value):
    read = spec._reader(spec.ROOT, name)
    assert read(SimpleNamespace(spans=_planted())) == pytest.approx(value)


@pytest.mark.parametrize("name", spans.METRICS)
def test_reader_reads_nothing_in_an_untraced_run(name):
    read = spec._reader(spec.ROOT, name)
    assert read(SimpleNamespace(served=[])) is None  # no spans at all
    assert read(SimpleNamespace(spans=[])) is None


def test_request_sums():
    (_, inner), *_ = spans.requests(_planted())
    row = spans.summed(inner)
    assert row["prefill"] == pytest.approx(104.0)
    assert row["prefill.device_wait"] == pytest.approx(4.0)
    assert (row["jit.trace"], row["jit.lower"], row["jit.compile"]) == (
        pytest.approx(10.0), pytest.approx(30.0), pytest.approx(40.0))
    assert row["generate.tail"] == pytest.approx(20.0)


def test_idle_gap_goes_to_the_program_span_inside_prefill(tmp_path):
    # window 0-100 ms; the harness's prefill 10-30 holds the program's
    # prefill.dispatch 11-29, which holds a compile 12-25; the device is
    # busy 0-10 and 30-100, so the gap 10-30 (midpoint 20) lies in all three
    host = _events([(1, 0, 100), (2, 0, 100), (3, 10, 20), (4, 11, 18),
                    (5, 12, 13)])
    ops = _events([(11, 0, 10), (11, 30, 70)])
    text = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000000 {host} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.serve" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "prefill" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "prefill.dispatch" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "jit.compile" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000000 {ops} }}
  event_metadata {{ key: 11 value {{ id: 11 name: "fusion.1" }} }}
}}
"""
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    # the harness alone names the gap after its own prefill
    assert xplane.reduce_dir(tmp_path).idle_by_host == {
        "prefill": pytest.approx(0.020)}
    with spans.program_idle():
        idle = xplane.reduce_dir(tmp_path).idle_by_host
    assert idle == {"jit.compile": pytest.approx(0.020)}
    assert "jit.compile" not in xplane.LAYER_SPANS  # restored


def test_tiny_cell_with_the_program_tracer(tmp_path):
    from repro.launch import serve
    from repro.obs import Tracer
    from repro.serving import engine

    cell = spec.load_cell("tiny.cold", tiny_checkout(tmp_path,
                                                     with_src=False))
    tracer = Tracer()
    with spans.program_tracer(tracer):
        out = harness.measure(cell, 11, 1.0, False, time.perf_counter(),
                              devices=jax.devices(), compile_cache=False)
    assert out["correct"] is True
    assert engine.Engine.__name__ == "Engine"  # the patches are undone
    assert harness.window.__name__ == "window"
    assert serve.fleet_gateway.__name__ == "fleet_gateway"
    got = spans.requests([e for e in tracer.events() if e.end is not None])
    # set-up's two requests were dropped at the window's opening
    assert len(got) == out["attempted"] - out["failed"] > 0
    for serve_span, inner in got:
        names = {e.name for e in inner}
        assert {"route", "start_instance", "make_prefill_batch", "prefill",
                "prefill.dispatch", "decode", "generate.tail"} <= names
        assert serve_span.args["cold"] is True
    run = SimpleNamespace(spans=tracer.events())
    for name in spans.METRICS:
        assert spec._reader(spec.ROOT, name)(run) is not None, name
