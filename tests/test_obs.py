"""Observability plane (DESIGN.md §18): bounded ring, span tracer, flight
recorder, metrics registry, Chrome-trace export, and the span-accounting /
cost-model cross-checks — including the golden modeled-fleet replay that
`benchmarks/fig16_serverless.py` ships into the bench entry.

Everything here is jax-free and deterministic (the modeled plane emits
explicit virtual timestamps), so the module lives in the fast CI subset.
The real-plane counterpart — an `Engine.load` + decode producing a loadable
Perfetto trace on perf_counter walls — lives with the other jit tests in
tests/test_fastpath.py.
"""
import json
import math
import threading
import tracemalloc

import pytest

from repro.obs import (
    NULL_TRACER,
    BoundedLog,
    FlightRecorder,
    MetricsRegistry,
    SpanEvent,
    Tracer,
    chrome_trace,
    cost_model_ratios,
    obs_stats,
    percentile,
    request_accounting,
    trace_request,
)
from repro.obs.export import chrome_trace_json


# --------------------------------------------------------------- BoundedLog

def test_bounded_log_is_list_compatible_under_capacity():
    log = BoundedLog(8)
    log.extend([1, 2, 3])
    log.append(4)
    assert log == [1, 2, 3, 4]
    assert list(log) == [1, 2, 3, 4]
    assert len(log) == 4 and bool(log)
    assert log[0] == 1 and log[-1] == 4
    assert log[1:3] == [2, 3]
    assert log.tail(2) == [3, 4]
    assert log.dropped_events == 0


def test_bounded_log_drops_oldest_and_counts():
    log = BoundedLog(4, range(4))
    log.extend([4, 5, 6])
    assert log == [3, 4, 5, 6]  # newest survive, oldest dropped
    assert log.dropped_events == 3


def test_bounded_log_clear_keeps_drop_counter():
    log = BoundedLog(2, [1, 2, 3])
    assert log.dropped_events == 1
    log.clear()
    assert len(log) == 0 and not log
    assert log.dropped_events == 1  # events already lost stay counted


# ------------------------------------------------------------------- Tracer

def test_tracer_span_uses_injected_clock():
    ticks = iter([10.0, 10.5, 11.0])
    tr = Tracer(clock=lambda: next(ticks))
    with tr.span("load", track="eng:0", cat="engine", args={"model": "m"}):
        pass
    tr.instant("crash")  # third tick
    (span, inst) = tr.events()
    assert span == SpanEvent("load", "eng:0", 10.0, 10.5, "engine",
                             {"model": "m"}, threading.get_ident())
    assert span.duration == 0.5
    assert inst.begin == 11.0 and inst.end is None and inst.duration == 0.0


def test_tracer_opens_annotate_around_live_spans():
    """The profiler bridge: each live span runs inside `annotate(name)`,
    entered before the span's clock starts and left after it stops, so
    the profiler's interval holds the tracer's; emits are not annotated."""
    log = []
    ticks = iter([1.0, 2.0, 3.0, 4.0])

    class Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    def clock():
        t = next(ticks)
        log.append(("clock", t))
        return t

    tr = Tracer(clock=clock, annotate=Ann)
    with tr.span("serve"):
        with tr.span("route"):
            pass
    tr.emit("req", 0.0, 1.0)  # a finished interval: nothing to annotate
    assert log == [("enter", "serve"), ("clock", 1.0), ("enter", "route"),
                   ("clock", 2.0), ("clock", 3.0), ("exit", "route"),
                   ("clock", 4.0), ("exit", "serve")]
    assert [(e.name, e.begin, e.end) for e in tr.events()] == [
        ("route", 2.0, 3.0), ("serve", 1.0, 4.0), ("req", 0.0, 1.0)]
    # a span that raises still leaves its annotation and is recorded
    log.clear()
    ticks = iter([5.0, 6.0])
    with pytest.raises(ValueError):
        with tr.span("load"):
            raise ValueError
    assert log[0] == ("enter", "load") and log[-1] == ("exit", "load")
    # the disabled tracer has no factory to call
    assert not hasattr(NULL_TRACER, "annotate")
    assert NULL_TRACER.span("serve") is NULL_TRACER.span("route")


def test_tracer_emit_takes_explicit_virtual_timestamps():
    tr = Tracer()  # the modeled plane never calls the clock
    tr.emit("prefill", 100.0, 100.25, track="req:0")
    (ev,) = tr.events()
    assert (ev.begin, ev.end, ev.cat) == (100.0, 100.25, "phase")


def test_tracer_thread_interleaved_emits_are_lossless():
    tr = Tracer(max_events=65536)

    def worker(tid):
        for i in range(500):
            tr.emit(f"s{i}", float(i), float(i) + 1.0, track=f"t{tid}")
            tr.instant(f"i{i}", float(i), track=f"t{tid}")

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = tr.events()
    assert len(evs) == 8 * 1000 and tr.dropped_events == 0
    # per-track order preserved (each thread appends monotonically)
    for tid in range(8):
        mine = [e for e in evs if e.track == f"t{tid}" and e.end is not None]
        assert [e.begin for e in mine] == sorted(e.begin for e in mine)


def test_tracer_ring_bounds_trace_and_counts_drops():
    tr = Tracer(max_events=16)
    for i in range(40):
        tr.emit("e", float(i), float(i) + 1.0)
    assert len(tr.events()) == 16
    assert tr.dropped_events == 24
    assert [e.begin for e in tr.tail(4)] == [36.0, 37.0, 38.0, 39.0]


def test_null_tracer_returns_singletons_and_collects_nothing():
    s1 = NULL_TRACER.span("a", track="x")
    s2 = NULL_TRACER.span("b")
    assert s1 is s2  # ONE cached null span, no per-call allocation
    with s1:
        pass
    NULL_TRACER.emit("e", 0.0, 1.0)
    NULL_TRACER.instant("i")
    NULL_TRACER.record_fault("f")
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.events() == [] and NULL_TRACER.tail(5) == []
    assert NULL_TRACER.dropped_events == 0


def test_disabled_tracer_is_allocation_free_on_the_hot_path():
    """The decode hot loop pays one attribute load + branch when tracing is
    off (`Engine.decode_many` pins this pattern): after warmup, thousands
    of guarded calls must retain no allocations at all."""
    tracer = NULL_TRACER

    def hot(n):
        for _ in range(n):
            if tracer.enabled:  # the instrumentation-site idiom
                with tracer.span("decode.step", cat="decode"):
                    pass
            tracer.emit("decode.step", 0.0, 1.0)  # even unguarded calls
            tracer.instant("p")

    hot(100)  # warm up bytecode/method caches before measuring
    tracemalloc.start()
    hot(10_000)
    retained, _peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert retained <= 256, f"disabled tracer retained {retained} bytes"


# ---------------------------------------------------------- flight recorder

def test_record_fault_dumps_the_timeline_leading_in():
    tr = Tracer(flight=FlightRecorder(last_n=3))
    for i in range(5):
        tr.emit(f"e{i}", float(i), float(i) + 1.0)
    tr.record_fault("engine.crash", 99.0, args={"engine": "eng0"})
    (dump,) = tr.flight.dumps
    assert dump["reason"] == "engine.crash" and dump["ts"] == 99.0
    # the newest last_n events INCLUDING the fault instant itself
    assert [e.name for e in dump["events"]] == ["e3", "e4", "engine.crash"]
    (fault,) = [e for e in tr.events() if e.cat == "fault"]
    assert fault.track == "faults" and fault.args == {"engine": "eng0"}


def test_flight_recorder_keeps_only_newest_dumps():
    tr = Tracer(flight=FlightRecorder(last_n=2, max_dumps=2))
    for i in range(4):
        tr.record_fault(f"f{i}", float(i))
    assert [d["reason"] for d in tr.flight.dumps] == ["f2", "f3"]
    assert tr.flight.dumps.dropped_events == 2


# ------------------------------------------- span accounting + cost ratios

def _emit_request(tr, rid, *, ttft, phases, preds=None):
    trace_request(tr, rid=rid, model_id="m", arrival=10.0 * rid, ttft=ttft,
                  phases=phases, decode_s=0.5, cold=True, engine="eng0",
                  preds=preds)


def test_request_accounting_identity_holds_when_phases_cover_ttft():
    tr = Tracer()
    _emit_request(tr, 0, ttft=1.0,
                  phases=[("queue", 0.2), ("load", 0.5), ("prefill", 0.3)])
    acct = request_accounting(tr.events())
    assert acct["n_requests"] == 1 and acct["violations"] == 0
    assert acct["unattributed_frac"] == pytest.approx(0.0, abs=1e-12)
    assert acct["phase_seconds"] == pytest.approx(
        {"queue": 0.2, "load": 0.5, "prefill": 0.3})
    # decode is traced but NOT part of the TTFT identity
    assert acct["attributed_total"] == pytest.approx(1.0)


def test_request_accounting_flags_a_phase_billed_without_a_span():
    """The detector the plane exists for: TTFT includes a phase nobody
    emitted a span for (the queue_s fold-in bug class) -> that request
    violates the identity and the aggregate gap is visible."""
    tr = Tracer()
    _emit_request(tr, 0, ttft=1.0,
                  phases=[("queue", 0.2), ("load", 0.5), ("prefill", 0.3)])
    _emit_request(tr, 1, ttft=1.0,  # 0.2 s of TTFT owned by no span
                  phases=[("load", 0.5), ("prefill", 0.3)])
    acct = request_accounting(tr.events())
    assert acct["n_requests"] == 2 and acct["violations"] == 1
    assert acct["unattributed_frac"] == pytest.approx(0.1)


def test_request_accounting_ignores_engine_tracks():
    tr = Tracer()
    _emit_request(tr, 0, ttft=1.0, phases=[("load", 1.0)])
    # engine-internal phases (h2d chunks, store reads) share the trace but
    # live on eng:* tracks — they must not double-count into the identity
    tr.emit("h2d.chunk", 0.0, 0.4, track="eng:eng0", cat="h2d")
    acct = request_accounting(tr.events())
    assert acct["violations"] == 0
    assert acct["attributed_total"] == pytest.approx(1.0)


def test_cost_model_ratios_measured_vs_predicted():
    tr = Tracer()
    _emit_request(tr, 0, ttft=1.0, phases=[("load", 0.8), ("prefill", 0.2)],
                  preds={"load": 0.4, "prefill": 0.2})
    ratios = cost_model_ratios(tr.events())
    assert ratios["load"] == pytest.approx(2.0)  # measured 2x the price
    assert ratios["prefill"] == pytest.approx(1.0)
    assert all(math.isfinite(r) for r in ratios.values())


def test_cost_model_ratios_zero_pred_zero_measured_reads_agreement():
    tr = Tracer()
    tr.emit("init", 5.0, 5.0, track="req:0", args={"pred": 0.0})
    assert cost_model_ratios(tr.events()) == {"init": 1.0}


# ------------------------------------------------------------ chrome export

def test_chrome_trace_tracks_become_named_thread_lanes():
    tr = Tracer()
    tr.emit("load", 1.0, 2.5, track="eng:0", cat="engine")
    tr.instant("crash", 3.0, track="faults", args={"engine": "eng0"})
    doc = chrome_trace(tr.events())
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert [m["args"]["name"] for m in meta] == ["eng:0", "faults"]
    (span,) = [e for e in evs if e["ph"] == "X"]
    assert (span["ts"], span["dur"]) == (1e6, 1.5e6)  # seconds -> us
    (inst,) = [e for e in evs if e["ph"] == "i"]
    assert inst["s"] == "t" and inst["args"] == {"engine": "eng0"}
    # spans and instants on different tracks get different tids
    assert span["tid"] != inst["tid"]


def test_chrome_trace_json_is_deterministic_and_loadable():
    def build():
        tr = Tracer()
        _emit_request(tr, 0, ttft=1.0, phases=[("load", 1.0)],
                      preds={"load": 1.0})
        tr.emit("h2d", -0.0, 0.0, track="eng:0")  # signed-zero clock math
        return chrome_trace_json(tr.events())

    a, b = build(), build()
    assert a == b
    doc = json.loads(a)
    assert "-0.0" not in a  # normalized, so replays serialize identically
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


# ------------------------------------------------- golden modeled-fleet run

def _traced_fleet_run(tracer, *, faults=()):
    from repro.core.trace import PAPER_MODELS
    from repro.serverless import ModeledFleetGateway
    from repro.serverless.workload import make_trace

    models = PAPER_MODELS[4:8]
    trace = make_trace("poisson", n_requests=40, seed=3, models=models,
                       mean_interarrival=20.0, max_output_tokens=64)
    fg = ModeledFleetGateway(models, n_engines=2, pool_bytes=int(20e9),
                             host_cache_bytes=int(24e9), seed=3,
                             keep_alive="fixed:40", tracer=tracer)
    fg.run_trace(trace, faults=list(faults))
    return fg


def test_fleet_replay_serializes_bit_identically():
    """The modeled plane emits virtual trace-clock timestamps, never wall
    clocks: the same seed must produce the same bytes."""
    t1, t2 = Tracer(), Tracer()
    _traced_fleet_run(t1)
    _traced_fleet_run(t2)
    assert len(t1.events()) > 0
    assert chrome_trace_json(t1.events()) == chrome_trace_json(t2.events())
    assert obs_stats(t1) == obs_stats(t2)


def test_fleet_span_identity_and_cost_ratios_golden():
    tracer = Tracer()
    fg = _traced_fleet_run(tracer)
    # attaching the tracer must not perturb the run itself
    assert fg.summary() == _traced_fleet_run(None).summary()
    obs = obs_stats(tracer)
    assert obs["n_requests"] == 40
    assert obs["violations"] == 0
    assert obs["unattributed_frac"] <= 1e-9  # identity exact, not just <2%
    assert obs["dropped_events"] == 0
    # the modeled plane prices every billed phase: ratios pin at 1.0, and
    # a phase folded into TTFT without a price would break this
    assert set(obs["span_cost_ratio"]) == {"init", "load", "profile",
                                           "prefill"}
    for phase, ratio in obs["span_cost_ratio"].items():
        assert ratio == pytest.approx(1.0), f"{phase} drifted: {ratio}"


def test_fleet_fault_auto_dumps_flight_recorder():
    from repro.serverless.workload import FaultEvent

    tracer = Tracer(flight=FlightRecorder(last_n=64))
    fg = _traced_fleet_run(tracer, faults=[
        FaultEvent(time=120.0, engine_id="engine0", recover_after=30.0)])
    assert fg.summary()["engine_crashes"] == 1
    (dump,) = tracer.flight.dumps
    assert dump["reason"] == "engine.crash" and dump["ts"] == 120.0
    assert any(e.cat == "fault" for e in dump["events"])
    recoveries = [e for e in tracer.events() if e.name == "engine.recover"]
    assert len(recoveries) == 1 and recoveries[0].begin == 150.0


# ------------------------------------------------- typed snapshot key order

def test_typed_snapshots_pin_legacy_key_orders():
    """The §18 migration moved hand-assembled summary dicts onto frozen
    dataclasses; these literals ARE the legacy key orders golden tests and
    check_bench read — a field reorder must fail here, not downstream."""
    from repro.stats import (ClusterSummaryStats, EngineFaultStats,
                             ModeledFaultStats, ObsStats)

    assert list(ClusterSummaryStats().as_dict()) == [
        "n", "ttft_mean", "ttft_p50", "ttft_p99", "load_mean", "warm_frac",
        "joined_frac", "reuse_frac_mean", "bytes_from_store_total",
        "bytes_store_hidden_total", "prefetched_frac", "makespan",
        "throughput_rps"]
    assert list(ModeledFaultStats().as_dict()) == [
        "injected", "store_retries", "crashes"]
    assert list(EngineFaultStats().as_dict()) == [
        "injected", "store_read_errors", "store_checksum_failures",
        "store_quarantined", "store_retries", "store_quarantines",
        "h2d_retries", "h2d_stalls", "transfer_timeouts", "prefetch_errors",
        "worker_restarts", "join_failovers", "load_errors",
        "shutdown_join_timeouts", "prefetch_pins_dropped", "tensors_reinit",
        "crashes"]
    assert list(ObsStats().as_dict()) == [
        "n_requests", "ttft_total", "attributed_total", "unattributed_frac",
        "violations", "phase_seconds", "span_cost_ratio", "trace_events",
        "dropped_events"]


def test_modeled_engine_fault_summary_uses_typed_snapshot():
    from repro.core.costmodel import PhaseCosts, paper_l40
    from repro.serverless.fleet import ModeledEngine

    eng = ModeledEngine("e0", int(1e9), costs=PhaseCosts(paper_l40()))
    assert list(eng.fault_summary()) == ["injected", "store_retries",
                                        "crashes"]


# --------------------------------------------------------- metrics registry

def test_metrics_registry_instruments_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("loads").inc()
    reg.counter("loads").inc(2)
    assert reg.counter("loads") is reg.counter("loads")  # get-or-create
    reg.counter("evictions").inc(0)
    snap = reg.snapshot().as_dict()
    assert snap == {"counters": {"evictions": 0, "loads": 3}}


def test_registry_absorbs_legacy_nested_counter_dicts():
    reg = MetricsRegistry()
    reg.absorb({"crashes": 2, "injected": {"store.read": 3},
                "skip_me": "str", "flag": True}, prefix="faults.")
    snap = reg.snapshot().as_dict()
    assert snap["counters"] == {"faults.crashes": 2,
                                "faults.injected.store.read": 3}


def test_percentile_convention_is_the_shared_one():
    # core.trace re-exports THIS function — one index convention everywhere
    from repro.core.trace import percentile as core_percentile

    assert core_percentile is percentile
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert percentile(xs, 0.5) == 3.0  # sorted[int(5*0.5)] = sorted[2]
    assert percentile(xs, 0.99) == 5.0  # clamped to the last sample
    assert percentile([], 0.5) == 0.0
