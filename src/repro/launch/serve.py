"""Serving launcher: a Tangram engine worker over the assigned architectures.

Registers the requested models, then serves a model-switching request
sequence, printing the Tangram load report (reuse fraction, bytes moved) and
TTFT phases per request — the single-worker real-data-plane version of the
cluster simulation.

  PYTHONPATH=src python -m repro.launch.serve \
      --models llama3.2-1b,deepseek-7b --smoke --requests 8

``--smoke`` (the default) serves toy widths for CPU runs; ``--no-smoke``
serves the published widths, and ``--num-layers N`` cuts each model to its
first N layers (printed as ``reduced``).  ``main(argv)`` returns the
engines, and on a trace replay the gateway and its metrics sink, so a
caller such as ``chip_smoke.py`` can check the run.

With ``--trace {poisson,diurnal,burst}`` the launcher replays a synthesized
serverless workload through the control-plane Gateway instead of the
round-robin sequence (DESIGN.md §13): arrivals follow the chosen process,
``--keep-alive-policy`` (zero | fixed[:T] | adaptive[:P]) drives per-model
scale-to-zero / retain on the trace clock, and the run ends with cold-start
rate + TTFT percentile summaries from the metrics sink.

  PYTHONPATH=src python -m repro.launch.serve \
      --models llama3.2-1b,deepseek-7b --trace poisson --requests 8 \
      --keep-alive-policy adaptive

With ``--n-engines N`` (N >= 2, requires ``--trace``) the trace replays
through the multi-engine ``FleetGateway`` instead (DESIGN.md §14): each
engine owns its own device pool + host Model Store, arrivals route by the
shared eq3+queue affinity score, and ``--prewarm`` additionally promotes
models AHEAD of their predicted re-arrivals when the cost/benefit check
passes (adaptive keep-alive only — fixed TTLs carry no arrival model).

``--chaos`` (requires ``--trace``) arms the seeded chaos schedule
(DESIGN.md §15): per-engine h2d stalls and a prefetch-worker death, plus an
engine crash/recover on the fleet path; the run ends with the per-engine
fault ledger and (fleet) the dropped/redriven counts.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import time
from typing import Any, NamedTuple

import jax

from repro.configs import SHAPES, ModelConfig, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serverless.gateway import generate
from repro.serving.engine import Engine


def _print_ttft_breakdown(records):
    """Per-phase TTFT breakdown table over a replay's TTFTRecords: where
    the time-to-first-token actually went, phase by phase (DESIGN.md §18)."""
    from repro.core.trace import percentile

    n = len(records)
    if n == 0:
        return
    ttft_total = sum(r.ttft for r in records) or 1e-12
    print("TTFT breakdown (decode excluded):")
    print(f"  {'phase':8s} {'mean':>9s} {'p95':>9s} {'share':>7s}")
    for phase in ("queue", "init", "load", "profile", "prefill"):
        xs = sorted(getattr(r, f"{phase}_s") for r in records)
        total = sum(xs)
        print(f"  {phase:8s} {total / n:8.3f}s {percentile(xs, 0.95):8.3f}s "
              f"{total / ttft_total:6.1%}")
    print(f"  {'ttft':8s} {ttft_total / n:8.3f}s "
          f"{percentile(sorted(r.ttft for r in records), 0.95):8.3f}s "
          f"{1.0:6.1%}")
    # the program's own first-token stamp, from the serve's start: the
    # phases above plus the gateway's host time, less the queue
    xs = sorted(r.first_token_s for r in records)
    print(f"  {'first':8s} {sum(xs) / n:8.3f}s {percentile(xs, 0.95):8.3f}s "
          f"{'':>7s}")


def _export_obs(tracer, args, extra_summary=None):
    """Write --trace-out (Perfetto JSON) and --metrics-out (unified metrics
    snapshot) from the run's tracer."""
    if tracer is None:
        return
    if args.trace_out:
        from repro.obs import write_chrome_trace

        write_chrome_trace(tracer.events(), args.trace_out)
        print(f"trace written: {args.trace_out} "
              f"({len(tracer.events())} events — load at ui.perfetto.dev)")
    if args.metrics_out:
        import json

        from repro.obs import MetricsRegistry, obs_stats

        reg = MetricsRegistry()
        if extra_summary:
            reg.absorb(extra_summary, prefix="summary")
        snap = reg.snapshot().as_dict()
        snap["obs"] = obs_stats(tracer)
        with open(args.metrics_out, "w") as f:
            json.dump(snap, f, indent=2, sort_keys=True)
        print(f"metrics written: {args.metrics_out}")


class Served(NamedTuple):
    """What one launcher run leaves behind, for a caller that checks it
    (`chip_smoke.py`): the engines, and on a trace replay the gateway and
    its metrics sink (both None for the round-robin sequence)."""

    engines: list
    gateway: Any = None
    sink: Any = None


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="llama3.2-1b,deepseek-7b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="toy widths for CPU runs; --no-smoke serves the "
                         "published widths")
    ap.add_argument("--num-layers", type=int, default=None,
                    help="cut every model to its first N layers (a depth "
                         "cut, printed as `reduced`)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--pool-mb", type=int, default=512)
    ap.add_argument("--host-cache-mb", type=int, default=None,
                    help="bound the host Model Store tier (spills beyond)")
    ap.add_argument("--no-prefetch", dest="prefetch", action="store_false",
                    help="disable the next-request prefetch hint (§12)")
    from repro.serverless.workload import ARRIVALS

    ap.add_argument("--trace", default=None, choices=list(ARRIVALS),
                    help="replay a synthesized serverless workload through "
                         "the control-plane Gateway (§13)")
    ap.add_argument("--keep-alive-policy", default="fixed:60",
                    help="zero | fixed[:T] | adaptive[:P] (with --trace)")
    ap.add_argument("--mean-interarrival", type=float, default=20.0,
                    help="trace mean inter-arrival seconds (with --trace)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--n-engines", type=int, default=1,
                    help="with --trace: route across N engines via the "
                         "FleetGateway's shared affinity score (§14); with "
                         "at least N local devices, engine i owns device i")
    ap.add_argument("--prewarm", action="store_true",
                    help="with --n-engines: promote models ahead of "
                         "predicted re-arrivals (adaptive keep-alive)")
    ap.add_argument("--chaos", action="store_true",
                    help="with --trace: arm the seeded chaos schedule "
                         "(DESIGN.md §15) — one h2d stall + one prefetch-"
                         "worker death per engine, plus an engine crash/"
                         "recover on the fleet path — and print the fault "
                         "ledger at the end")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="FILE.json",
                    help="write the run's span timeline as Chrome/Perfetto "
                         "trace-event JSON (DESIGN.md §18) — load it at "
                         "ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="FILE.json",
                    help="write the unified metrics snapshot (summary "
                         "counters + span accounting) as JSON")
    args = ap.parse_args(argv)
    if args.n_engines < 1:
        ap.error("--n-engines must be >= 1")
    if args.n_engines > 1 and args.trace is None:
        ap.error("--n-engines > 1 requires --trace (fleet replay)")
    if args.chaos and args.trace is None:
        ap.error("--chaos requires --trace (fault schedules replay on the "
                 "trace clock)")
    if args.num_layers is not None and args.num_layers < 1:
        ap.error("--num-layers must be >= 1")
    return args


def model_configs(args) -> dict[str, ModelConfig]:
    """The served configurations: toy widths under --smoke, then the
    --num-layers depth cut."""
    cfgs = {}
    for n in args.models.split(","):
        cfg = get_config(n)
        if args.smoke:
            cfg = cfg.smoke()
        if args.num_layers is not None and args.num_layers < cfg.num_layers:
            print(f"reduced: {n} num_layers {cfg.num_layers} -> "
                  f"{args.num_layers}")
            cfg = dataclasses.replace(
                cfg, num_layers=args.num_layers,
                layer_pattern=cfg.layer_pattern[: args.num_layers])
        cfgs[n] = cfg
    return cfgs


def build_engines(args, cfgs, *, devices=None, injectors=None,
                  tracer=None) -> list[Engine]:
    """One engine per entry of `devices` (default: engine i on local device
    i when there are at least --n-engines of them, else JAX's default
    device), each with every model of `cfgs` registered."""
    if devices is None:
        local = jax.local_devices()
        n = args.n_engines
        devices = local[:n] if len(local) >= n else [None] * n
    host_bytes = (None if args.host_cache_mb is None
                  else args.host_cache_mb * 1024 * 1024)
    engines = [Engine(args.pool_mb * 1024 * 1024, host_cache_bytes=host_bytes,
                      engine_id=f"engine{i}",
                      faults=injectors[i] if injectors else None,
                      tracer=tracer, device=dev)
               for i, dev in enumerate(devices)]
    for eng in engines:
        for n, cfg in cfgs.items():
            eng.register(n, cfg)
    return engines


def make_replay_trace(args):
    """The synthesized serverless workload over --models (with --trace)."""
    from repro.core.trace import SimModel
    from repro.serverless import make_trace

    sim_models = [SimModel(n, 1e6, 1) for n in args.models.split(",")]
    return make_trace(args.trace, n_requests=args.requests,
                      models=sim_models, seed=args.trace_seed,
                      mean_interarrival=args.mean_interarrival)


def fleet_gateway(args, engines, tracer=None):
    """The multi-engine FleetGateway over `engines`, configured from args."""
    from repro.serverless import FleetGateway

    return FleetGateway(engines, keep_alive=args.keep_alive_policy,
                        prefetch=args.prefetch, prewarm=args.prewarm,
                        prompt_len=args.prompt_len,
                        gen_tokens=args.gen_tokens, tracer=tracer)


def main(argv=None) -> Served:
    args = parse_args(argv)
    use_compile_cache()

    injectors = None
    fault_events = []
    if args.chaos:
        # seeded chaos schedule, one injector PER engine (the fleet ledger
        # sums per-engine injectors — sharing one would double-count).  The
        # launcher leaves store_keys empty: keyed store.read specs name
        # tensor fingerprints, which fig17 and tests/test_chaos.py control;
        # here the h2d stall, worker death, and fleet crash/recover fire.
        from repro.core.faults import FaultInjector
        from repro.serverless.workload import chaos_schedule

        specs, fault_events = chaos_schedule(seed=args.chaos_seed,
                                             n_engines=args.n_engines)
        injectors = [FaultInjector(specs=tuple(s), seed=args.chaos_seed)
                     for s in specs]

    # obs plane (DESIGN.md §18): one tracer across the engines and the
    # gateway — engine spans stamp perf_counter walls, request span
    # families ride the virtual trace clock, each on its own track
    tracer, uninstall_jit = None, lambda: None
    if args.trace_out or args.metrics_out:
        from repro.obs import FlightRecorder, Tracer
        from repro.obs import jit as obs_jit

        # the spans also enter a profiler trace, if one is running, and
        # JAX's compile steps are spans of their own
        tracer = Tracer(flight=FlightRecorder(),
                        annotate=jax.profiler.TraceAnnotation)
        uninstall_jit = obs_jit.install(tracer)

    names = args.models.split(",")
    cfgs = model_configs(args)
    engines = build_engines(args, cfgs, injectors=injectors, tracer=tracer)
    engine = engines[0]

    if args.trace is not None:
        # serverless control plane (§13): synthesize the arrival process
        # over the registered models and replay it through the Gateway —
        # keep-alive decisions run on the trace clock, phase durations are
        # measured wall time
        from repro.serverless import Gateway

        trace = make_replay_trace(args)
        if args.n_engines > 1:
            # fleet replay (§14): shared-score routing + optional pre-warm
            gw = fleet_gateway(args, engines, tracer)
            sink = gw.run_trace(trace, faults=fault_events)
            for i, (r, d) in enumerate(zip(sink.records, gw.decisions)):
                print(f"req {i}: {r.model_id:16s} -> {d[2]} "
                      f"{'cold' if r.cold else 'warm'} "
                      f"load {r.load_s*1e3:7.1f}ms "
                      f"prefill {r.prefill_s:.2f}s")
        else:
            gw = Gateway(engine, keep_alive=args.keep_alive_policy,
                         prefetch=args.prefetch, prompt_len=args.prompt_len,
                         gen_tokens=args.gen_tokens, tracer=tracer)
            sink = gw.run_trace(trace)
            for i, r in enumerate(sink.records):
                print(f"req {i}: {r.model_id:16s} "
                      f"{'cold' if r.cold else 'warm'} "
                      f"load {r.load_s*1e3:7.1f}ms prefill {r.prefill_s:.2f}s "
                      f"decode {r.decode_s/max(args.gen_tokens,1)*1e3:.0f}ms/tok")
        s = sink.summary()
        ls = gw.lifecycle.summary()
        fleet_note = (f" engines={args.n_engines} "
                      f"prewarms={gw.prewarms} hits={gw.prewarm_hits}"
                      if args.n_engines > 1 else "")
        print(f"serverless summary: n={s['n']} "
              f"cold_rate={s['cold_start_rate']:.2f} "
              f"ttft_p50={s['ttft_p50']:.2f}s ttft_p95={s['ttft_p95']:.2f}s "
              f"expirations={int(ls['expirations'])} "
              f"policy={args.keep_alive_policy} trace={args.trace}"
              f"{fleet_note}")
        if args.chaos:
            for eng in engines:
                fs = eng.fault_summary()
                print(f"chaos[{eng.engine_id}]: injected={fs['injected']} "
                      f"h2d_stalls={fs['h2d_stalls']} "
                      f"h2d_retries={fs['h2d_retries']} "
                      f"worker_restarts={fs['worker_restarts']} "
                      f"join_failovers={fs['join_failovers']} "
                      f"quarantined={fs['store_quarantined']} "
                      f"crashes={fs['crashes']}")
            if args.n_engines > 1:
                fsum = gw.summary()
                print(f"chaos fleet: dropped={fsum['dropped_requests']} "
                      f"crashes={fsum['engine_crashes']} "
                      f"recoveries={fsum['engine_recoveries']} "
                      f"redriven={fsum['requests_redriven']}")
        _print_ttft_breakdown(sink.records)
        uninstall_jit()
        _export_obs(tracer, args, extra_summary=s)
        for eng in engines:
            eng.close()
        return Served(engines, gw, sink)

    seq = list(itertools.islice(itertools.cycle(names), args.requests))
    for i, name in enumerate(seq):
        t0 = time.time()
        rep = engine.load(name)
        load_s = time.time() - t0
        if args.prefetch and i + 1 < len(seq) and seq[i + 1] != name:
            # the launcher IS the scheduler here: the next placement is
            # already known, so hint it now — its store-tier tensors promote
            # in the background while this request prefills/decodes (§12)
            engine.prefetch(seq[i + 1])
        inst = engine.start_instance(name, num_pages=128)
        model = build_model(cfgs[name])
        shape = dataclasses.replace(SHAPES["train_4k"], seq_len=args.prompt_len,
                                    global_batch=2, kind="prefill")
        batch = model.make_batch(jax.random.PRNGKey(i), shape)
        _, prefill_s, decode_s, _ = generate(inst, batch, args.gen_tokens)
        inst.finish()
        stats = engine.last_load
        pf = (f" prefetched={stats.bytes_prefetched/1e6:.1f}MB"
              if stats.bytes_prefetched else "")
        print(f"req {i}: {name:16s} reuse={rep.reuse_fraction:4.0%} "
              f"transferred={rep.bytes_transferred/1e6:6.1f}MB "
              f"(modeled load {rep.load_seconds*1e3:6.1f}ms, wall {load_s:.2f}s) "
              f"prefill {prefill_s:.2f}s decode {decode_s/args.gen_tokens*1e3:.0f}ms/tok "
              f"pool_free={engine.store.free_bytes()/1e6:.0f}MB{pf}")
    uninstall_jit()
    _export_obs(tracer, args)
    engine.close()
    return Served(engines)


if __name__ == "__main__":
    main()
