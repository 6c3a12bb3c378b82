#!/usr/bin/env python3
"""Serve on a TPU chip through the normal entry point, and check the result.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four engines, one per chip

One chip: `repro.launch.serve.main` replays a seeded poisson trace of six
requests over deepseek-7b and yi-9b.  Both models run at their published
widths, cut to 8 layers, with random weights from a seed.  The trace gives
cold starts, warm hits and a model switch.  The script then checks on the
chip that:

  * the compiled decode step holds the Pallas kernel (`tpu_custom_call`);
  * one request's decode logits through the kernel match the XLA
    reference within the bf16 tolerance of tests/test_kernels.py;
  * reloading a resident model moves no bytes (reuse=100%);
  * every counter of every engine's fault ledger is 0;
  * every request got all its tokens.

`--chips 4` runs only the fleet phase.  The same trace replays through a
FleetGateway over four engines, engine i on chip i.  It is compared with
the trace through a one-engine FleetGateway on chip 0, in the same process.
Each request's tokens must be equal, each engine's tensors and KV slab must
live on its own chip, and at least two engines must serve requests.

Without a TPU, the script exits non-zero and prints no result line.  It
prints walls and peak HBM for the one run it makes: a smoke run, not a
measurement.  The last line of its standard output is one JSON object,
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# the served path at published widths: ~8.7 GB of bf16 weights for the two
# models together, a pool that holds both, KV pages on top
CHIP_ARGV = ["--models", "deepseek-7b,yi-9b", "--no-smoke", "--num-layers", "8",
             "--trace", "poisson", "--requests", "6", "--trace-seed", "0",
             "--pool-mb", "9216", "--prompt-len", "32", "--gen-tokens", "8"]
# the fleet phase: arrivals close enough that engine 0 is still busy when
# the next lands, so the affinity router spreads them over the chips
FLEET_ARGV = CHIP_ARGV + ["--mean-interarrival", "0.5"]
# tests/test_kernels.py: kernel vs reference, bf16
BF16_TOL = 2e-2
KERNEL_STEPS = 2  # decode steps compared between kernel and reference


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def serve_phase(serve, argv) -> list[str]:
    """Serve `argv` through the launcher, then check the run on its engine.
    Returns the failed checks (empty: all passed)."""
    import jax
    import jax.numpy as jnp

    from repro.serverless.gateway import make_prefill_batch
    from repro.serving import engine as engine_mod

    problems = []
    t0 = time.perf_counter()
    served = serve.main(argv)
    serve_s = time.perf_counter() - t0
    args = serve.parse_args(argv)
    eng = served.engines[0]
    records = served.sink.records

    if len(records) != args.requests:
        problems.append(f"{len(records)} of {args.requests} requests served")
    short = [i for i, r in enumerate(records)
             if len(r.tokens) != args.gen_tokens + 1]
    if short:
        problems.append(f"requests {short} got fewer than "
                        f"{args.gen_tokens + 1} tokens")
    print(f"requests: {len(records)} served, "
          f"{sum(r.cold for r in records)} cold, "
          f"{len({r.model_id for r in records})} models, "
          f"tokens of req 0: {list(records[0].tokens)}")

    t1 = time.perf_counter()
    model = records[-1].model_id
    rep = eng.load(model)
    moved = eng.last_load.bytes_h2d
    print(f"resident reload {model}: reuse={rep.reuse_fraction:.0%} "
          f"transferred={rep.bytes_transferred} bytes h2d={moved} bytes")
    if rep.reuse_fraction != 1.0 or rep.bytes_transferred or moved:
        problems.append(f"reload of resident {model} moved bytes")

    cfg = eng.models[model].cfg
    batch = make_prefill_batch(eng, model, args.prompt_len, seed=0)
    kern = eng.start_instance(model, num_pages=64, attn_mode="kernel")
    ref = eng.start_instance(model, num_pages=64, attn_mode="ref")
    lk = kern.prefill(batch)
    ref.prefill(batch)
    worst = 0.0
    for step in range(KERNEL_STEPS):
        tok = jnp.argmax(lk, -1).astype(jnp.int32)
        lk, lr = kern.decode(tok), ref.decode(tok)
        lk32, lr32 = lk.astype(jnp.float32), lr.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(lk32 - lr32))
                    / jnp.maximum(jnp.max(jnp.abs(lr32)), 1e-6))
        worst = max(worst, err)
    print(f"kernel vs reference logits ({model}, {KERNEL_STEPS} decode "
          f"steps): max relative error {worst:.3e} (tolerance {BF16_TOL})")
    if not worst < BF16_TOL:
        problems.append(f"kernel logits differ from reference by {worst:.3e}")

    hlo = engine_mod._paged_decode_step.lower(
        eng.params_of(model), cfg, tok, kern._tables, kern._lengths,
        kern.slab.k_pages, kern.slab.v_pages,
        attn="kernel").compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    print(f"decode step holds tpu_custom_call: {has_kernel}")
    if not has_kernel:
        problems.append("compiled decode step has no tpu_custom_call")
    kern.finish()
    ref.finish()

    for e in served.engines:
        ledger = e.fault_summary()
        nonzero = {k: v for k, v in ledger.items()
                   if (sum(v.values()) if isinstance(v, dict) else v)}
        print(f"fault ledger {e.engine_id}: "
              f"{'all zero' if not nonzero else nonzero}")
        if nonzero:
            problems.append(f"fault ledger of {e.engine_id}: {nonzero}")
    checks_s = time.perf_counter() - t1

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"smoke run (single run, not a metric): serve wall {serve_s:.3f}s, "
          f"checks wall {checks_s:.3f}s, "
          f"peak_bytes_in_use {peak if peak is not None else 'not reported'}")
    return problems


def _one_engine_tokens(serve, args, device) -> list[tuple[int, ...]]:
    """Each request's tokens from the trace through a one-engine
    FleetGateway on `device`.  The engine is unreachable on return, so its
    device memory can be freed."""
    [engine] = serve.build_engines(args, serve.model_configs(args),
                                   devices=[device])
    sink = serve.fleet_gateway(args, [engine]).run_trace(
        serve.make_replay_trace(args))
    engine.close()
    return [r.tokens for r in sink.records]


def fleet_phase(serve, argv, devices) -> list[str]:
    """Replay `argv`'s trace over one engine per device, and over one engine
    on devices[0]; compare.  Returns the failed checks."""
    import jax

    problems = []
    n = len(devices)
    args = serve.parse_args(argv)

    # the one-engine reference first, and gone before the fleet starts: the
    # fleet's engine 0 takes the same chip, and both would not fit there
    t0 = time.perf_counter()
    reference = _one_engine_tokens(serve, args, devices[0])
    gc.collect()
    ref_s = time.perf_counter() - t0
    held = sum(a.nbytes for a in jax.live_arrays()
               if devices[0] in a.devices())
    print(f"bytes still held on {devices[0]} after the reference: {held}")
    if held > 64 << 20:
        return problems + [f"the reference engine left {held} bytes on "
                           f"{devices[0]}"]

    t1 = time.perf_counter()
    served = serve.main(argv + ["--n-engines", str(n)])
    fleet_s = time.perf_counter() - t1
    tokens = [r.tokens for r in served.sink.records]
    if tokens != reference:
        diff = [i for i, (a, b) in enumerate(zip(tokens, reference)) if a != b]
        problems.append(f"fleet tokens differ from the one-engine replay "
                        f"(requests {diff}, {len(tokens)} vs "
                        f"{len(reference)} served)")
    for eng, dev in zip(served.engines, devices):
        arrays = list(eng._tensors.values())
        for slab in eng._slabs.values():
            arrays += [slab.k_pages, slab.v_pages]
        elsewhere = sum(a.devices() != {dev} for a in arrays)
        print(f"{eng.engine_id} on {dev}: {len(arrays)} arrays, "
              f"{elsewhere} elsewhere")
        if eng.device != dev or elsewhere:
            problems.append(f"{eng.engine_id}: {elsewhere} arrays not on "
                            f"{dev}")
    used = sorted({d[2] for d in served.gateway.decisions})
    print(f"engines that served: {used}; tokens equal to the one-engine "
          f"replay: {tokens == reference}")
    if len(used) < 2:
        problems.append(f"only {used} served requests")
    print(f"smoke run (single run, not a metric): reference wall "
          f"{ref_s:.3f}s, fleet wall {fleet_s:.3f}s")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-engine fleet phase")
    opts = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        return _fail(f"no repro package under {SRC}: run from the repo root")
    sys.path.insert(0, str(SRC))

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        return _fail(f"no TPU found: JAX sees {devices[0].platform}")
    if len(devices) < opts.chips:
        return _fail(f"--chips {opts.chips} needs {opts.chips} chips, "
                     f"JAX sees {len(devices)}")
    kind = devices[0].device_kind
    print(f"device: platform=tpu kind={kind} count={len(devices)}")

    from repro.launch import serve
    from repro.launch.compile_cache import use_compile_cache

    cache = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"compile cache: {use_compile_cache()}")

    if opts.chips == 4:
        problems = fleet_phase(serve, FLEET_ARGV, devices[:4])
    else:
        problems = serve_phase(serve, CHIP_ARGV)
    print(f"compile cache: {cache['hits']} entries read, "
          f"{cache['writes']} written")
    if problems:
        for p in problems:
            _fail(p)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": "tpu", "kind": kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
