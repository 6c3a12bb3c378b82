"""One run of one cell.

Set-up loads the cell's model into one engine behind the fleet gateway and
serves two requests of the cell's own shapes, which compiles (or loads
from the compile cache) every program the window runs and leaves the model
as the traffic expects it: warm, or scaled to zero.  The window is an open
loop on the wall clock: each due arrival goes to `FleetGateway.run_trace`
as soon as the server is free, and its time to first token runs from the
moment it was due.  After the window the program's state is freed and the
plain reference checks a sample of what the window served.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import itertools
import json
import random
import shutil
import sys
import tempfile
import time
from typing import Optional

import numpy as np

from bench import arrivals, reference, spec
from bench.peaks import peak
from bench.readers import percentile

# a minute past the close, requests still queued are served; later ones fail
GRACE_S = 60.0
# served tokens the check compares at least (whole requests, longest first)
CHECK_TOKENS = 256


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


@dataclasses.dataclass
class Served:
    """One request of the window, on the window's clock (seconds).  Every
    time here is read by the benchmark's own clock (`Server`)."""

    due: float
    start: float  # the gateway took it
    first: float  # the first token's logits were on the device
    end: float  # the program's `generate` returned the tokens
    rec: object  # the gateway's TTFTRecord
    prompt: np.ndarray  # (S,) prompt tokens

    @property
    def queue_s(self) -> float:
        return self.start - self.due

    @property
    def ttft_s(self) -> float:
        """Due time to first token."""
        return self.first - self.due

    @property
    def decode_s(self) -> float:
        """First token to the last, on the host."""
        return self.end - self.first


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: spec.Cell
    setup_s: float
    served: list[Served]
    attempted: int
    failed: int
    first_load: dict  # DataLoadStats of set-up's first load
    device_kind: str
    trace: Optional[object] = None  # xplane.Reduced, traced runs only

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    @property
    def arch(self):
        return self.cell.arch

    @property
    def peak(self):
        return peak(self.device_kind)

    def tpot_s(self) -> Optional[float]:
        steps = len(self.served) * self.traffic["gen_tokens"]
        if not steps:
            return None
        return sum(s.decode_s for s in self.served) / steps


# ------------------------------------------------------------ the server
class _FirstToken:
    """Stands in for an instance inside the program's `generate`, and
    stamps, on the benchmark's clock, the moment prefill's logits are on
    the device (the first token is their argmax, one small op later)."""

    def __init__(self, inst):
        self._inst, self.first = inst, None

    def __getattr__(self, name):
        return getattr(self._inst, name)

    def prefill(self, batch):
        import jax

        out = jax.block_until_ready(self._inst.prefill(batch))
        self.first = time.perf_counter()
        return out


class Server:
    """One engine and the fleet gateway over it, for one cell and seed."""

    def __init__(self, cell: spec.Cell, seed: int, device):
        from repro.launch.serve import fleet_gateway, model_configs, parse_args
        from repro.models import build_model
        from repro.serverless import fleet
        from repro.serving.engine import Engine

        import jax

        c, t, arch = cell.config, cell.traffic, cell.arch
        self.model_id = c["repro_model"]
        argv = ["--models", self.model_id,
                "--smoke" if c.get("repro_smoke") else "--no-smoke",
                "--pool-mb", str(c["pool_mb"]),
                "--keep-alive-policy", t["keep_alive"],
                "--prompt-len", str(t["prompt_len"]),
                "--gen-tokens", str(t["gen_tokens"]), *arch.launch_args(c)]
        args = parse_args(argv)
        cfg = model_configs(args)[self.model_id]
        arch.check_program(cfg, c)
        self.engine = Engine(args.pool_mb * 1024 * 1024, engine_id="engine0",
                             device=device)
        expected = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        self.engine.register(self.model_id, cfg,
                             init_fn=arch.program_init_fn(c, seed, expected))
        self.gw = fleet_gateway(args, [self.engine])
        # prompts are drawn per request from a counter: start it from the
        # seed, so each seed serves other prompts
        self.gw._req_seq = itertools.count((seed * 1_000_003) % (1 << 30))
        # keep each served prompt for the check
        self.prompts: list[np.ndarray] = []
        self._fleet = fleet
        self._make_batch = fleet.make_prefill_batch

        def capture(*a, **kw):
            batch = self._make_batch(*a, **kw)
            self.prompts.append(batch["tokens"])
            return batch

        fleet.make_prefill_batch = capture
        # first-token and last-token clocks of each request, read here and
        # not from the program's records
        self.clock: list[tuple[float, float]] = []
        self._generate = fleet.generate

        def timed(inst, batch, gen_tokens):
            stamp = _FirstToken(inst)
            out = self._generate(stamp, batch, gen_tokens)
            if stamp.first is None:
                raise RuntimeError("generate did not prefill: no first token")
            self.clock.append((stamp.first, time.perf_counter()))
            return out

        fleet.generate = timed
        # Reuse Store evictions, counted where they happen
        self.bytes_evicted = 0
        evict = self.engine.store._evict

        def counted(fp):
            n = evict(fp)
            self.bytes_evicted += n
            return n

        self.engine.store._evict = counted

    def serve(self, at: float):
        """One request through `run_trace` at trace time `at`; returns its
        record, its prompt, and the perf_counter clocks of its first and
        last token."""
        from repro.core.trace import Request

        t = self.gw.prompt_len, self.gw.gen_tokens
        n, m = len(self.gw.sink.records), len(self.clock)
        self.gw.run_trace([Request(time=at, model_id=self.model_id,
                                   dataset="bench", prompt_tokens=t[0],
                                   output_tokens=t[1], batch_size=1)])
        assert len(self.gw.sink.records) == n + 1, "request not recorded"
        assert len(self.clock) == m + 1, "request not generated"
        return (self.gw.sink.records[-1], self.prompts[-1]) + self.clock[-1]

    def close(self):
        self._fleet.make_prefill_batch = self._make_batch
        self._fleet.generate = self._generate
        self.engine.close()


# ------------------------------------------------------------ the window
class _Compiles:
    """Counts, while `on`, the programs JAX compiled and those it loaded
    from the persistent compile cache (JAX times a cache load as a compile
    request too)."""

    def __init__(self):
        import jax.monitoring as mon

        self.on, self.requests, self.loaded = False, 0, 0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def close(self):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._dur)
        mon.unregister_event_listener(self._ev)

    def _dur(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _ev(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.loaded


def _annotate(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def window(server: Server, due: list[float], seconds: float, *,
           annotate: bool = False):
    """Serve every arrival due in [0, seconds) on the wall clock; returns
    (served, wall seconds the window took).  A request that raises, or
    is still queued a minute past the close, is not served."""
    ann = _annotate if annotate else (lambda name: contextlib.nullcontext())
    served = []
    t0 = time.perf_counter()
    for a in due:
        now = time.perf_counter() - t0
        if now > seconds + GRACE_S:
            break
        if now < a:
            with ann("bench.idle"):
                time.sleep(a - now)
        start = time.perf_counter() - t0
        try:
            with ann("bench.serve"):
                rec, prompt, first, end = server.serve(start)
        except Exception as e:  # a request that raises has failed
            print(f"request due at {a:.3f}s failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            continue
        served.append(Served(a, start, first - t0, end - t0, rec,
                             np.asarray(prompt)[0]))
    return served, time.perf_counter() - t0


class _GcPauses:
    """Pauses of Python's garbage collector while `on`, by generation."""

    def __init__(self):
        self.on, self._t = False, 0.0
        self.count, self.total_s, self.max_s = [0] * 3, [0.0] * 3, [0.0] * 3
        gc.callbacks.append(self._cb)

    def close(self):
        gc.callbacks.remove(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            g, d = info["generation"], time.perf_counter() - self._t
            self.count[g] += 1
            self.total_s[g] += d
            self.max_s[g] = max(self.max_s[g], d)

    def __str__(self):
        return "; ".join(f"gen{g} {self.count[g]} x, {self.total_s[g]:.6f}s, "
                         f"max {self.max_s[g]:.6f}s" for g in range(3))


@contextlib.contextmanager
def _layer_spans():
    """Host spans around the calls into each layer, in the profiler's own
    trace, so that idle gaps of the device can be put down to them."""
    from repro.serverless import fleet
    from repro.serving import engine as E

    wrapped = [(E.Engine, "load"), (E.Engine, "start_instance"),
               (E.Instance, "prefill"), (E.Instance, "decode"),
               (E.Instance, "finish"), (fleet, "make_prefill_batch")]
    saved = [(o, n, getattr(o, n)) for o, n in wrapped]

    def wrap(fn, name):
        def inner(*a, **kw):
            with _annotate(name):
                return fn(*a, **kw)
        return inner

    for o, n, fn in saved:
        setattr(o, n, wrap(fn, n))
    try:
        yield
    finally:
        for o, n, fn in saved:
            setattr(o, n, fn)


# ------------------------------------------------------------ the check
def sample(served: list[Served], seed: int) -> list[Served]:
    """Requests for the check, drawn from the seed: the longest first, then
    others until CHECK_TOKENS served tokens are in."""
    if not served:
        return []
    longest = max(served, key=lambda s: len(s.rec.tokens))
    rest = [s for s in served if s is not longest]
    random.Random(seed).shuffle(rest)
    out, n = [longest], len(longest.rec.tokens)
    for s in rest:
        if n >= CHECK_TOKENS:
            break
        out.append(s)
        n += len(s.rec.tokens)
    return out


def check(arch, c: dict, seed: int, picked: list[Served], *,
          control: bool = False) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over the picked requests: `arch`'s reference of
    configuration `c`.  With `control`, also the same gap for the tokens
    the fp8 control puts first."""
    w = arch.make(c, seed)
    worst, worst_ctl = 0.0, 0.0
    for s in picked:
        toks = np.asarray(s.rec.tokens, np.int32)
        seq = np.concatenate([s.prompt, toks[:-1]])[None]
        first = len(s.prompt) - 1
        ref = arch.logits(w, c, seq, first)
        worst = max(worst, float(reference.gaps(ref, toks[None]).max()))
        if control:
            ctl = arch.logits(w, c, seq, first, fp8=True)
            pick = np.asarray(ctl).argmax(-1)
            worst_ctl = max(worst_ctl, float(reference.gaps(ref, pick).max()))
    out = {"max_logit_gap": worst, "tokens": sum(len(s.rec.tokens)
                                                  for s in picked)}
    if control:
        out["control_max_logit_gap"] = worst_ctl
    return out


# ------------------------------------------------------------ one run
def setup(cell: spec.Cell, seed: int, device) -> tuple[Server, dict]:
    """Build the server and serve the two warm-up requests; prints how
    long each step took."""
    t = [time.perf_counter()]
    server = Server(cell, seed, device)
    t.append(time.perf_counter())
    server.serve(-120.0)
    t.append(time.perf_counter())
    first = dataclasses.asdict(server.engine.last_load)
    server.serve(-60.0)
    t.append(time.perf_counter())
    print(f"setup: server built {t[1] - t[0]:.3f}s, first request (init, "
          f"first load) {t[2] - t[1]:.3f}s, second request "
          f"{t[3] - t[2]:.3f}s", flush=True)
    return server, first


def compile_cache_in_checkout() -> str:
    """JAX's persistent compile cache at `<checkout>/.jax_cache`, whatever
    JAX_COMPILATION_CACHE_DIR says, so that only a cell's first run in a
    checkout compiles and two checkouts share nothing.  Small programs are
    cached too: the served prefill is traced anew for every request, and
    under JAX's default (programs under 1 s are not cached) each request
    would compile it inside the window."""
    import jax

    path = str(spec.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            t_start: float, *, devices=None, control: bool = False,
            compile_cache: bool = True,
            requests_out: Optional[str] = None) -> dict:
    """One whole run; returns the result line's object.  `devices` skips
    the look for chips (tests on the CPU); `control` adds the fp8
    control's reading; `compile_cache=False` leaves JAX's persistent cache
    as the process has it; `requests_out` names a file for one JSON line
    of clocks per served request."""
    import jax

    if devices is None:
        devices = chips(cell.chips)
    if compile_cache:
        compile_cache_in_checkout()
    dev = devices[0]
    compiles, pauses = _Compiles(), _GcPauses()
    print(f"setup: process start to the server's build "
          f"{time.perf_counter() - t_start:.3f}s", flush=True)
    server, first = setup(cell, seed, dev)
    due = arrivals.schedule(cell.traffic, seconds)
    setup_s = time.perf_counter() - t_start

    spans = _layer_spans() if trace else contextlib.nullcontext()
    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # no span per Python call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
    compiles.on = pauses.on = True
    evicted0 = server.bytes_evicted
    try:
        with spans, (_annotate("bench.window") if trace
                     else contextlib.nullcontext()):
            served, wall = window(server, due, seconds, annotate=trace)
    finally:
        compiles.close()
        pauses.close()
        if trace:
            jax.profiler.stop_trace()
    evicted = server.bytes_evicted - evicted0
    late = [s.queue_s for s in served]
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    print(f"window: {len(due)} due, {len(served)} served, "
          f"{wall:.3f}s wall; generator late p50 "
          f"{_pct(late, 0.5):.6f}s p95 {_pct(late, 0.95):.6f}s max "
          f"{max(late, default=0.0):.6f}s; compiled in window "
          f"{compiles.compiled}, loaded from the compile cache in window "
          f"{compiles.loaded}; "
          f"store bytes evicted in window {evicted}; peak_bytes_in_use "
          f"{mem_peak}; gc pauses in window: {pauses}", flush=True)
    if requests_out:
        with open(requests_out, "w") as f:
            for s in served:
                f.write(json.dumps({
                    "due": s.due, "start": s.start, "first": s.first,
                    "end": s.end, "cold": s.rec.cold, "init_s": s.rec.init_s,
                    "load_s": s.rec.load_s, "profile_s": s.rec.profile_s,
                    "prefill_s": s.rec.prefill_s,
                    "decode_s": s.rec.decode_s}) + "\n")
    # raised, or not served by the end of the run
    run = Run(cell, setup_s, served, len(due),
              len(due) - len(served), first, dev.device_kind)

    reduced = None
    if trace:
        from bench import xplane

        try:
            reduced = xplane.reduce_dir(tmp, run, chips=[d.id for d in devices])
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        run.trace = reduced
    picked = sample(served, seed)
    server.close()
    del server
    gc.collect()

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = (setup_s if m.name == "setup_s" else m.read(run))
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    got = check(cell.arch, cell.config, seed, picked, control=control)
    limit = cell.config["check"]["max_logit_gap"]
    correct = (bool(picked) and run.failed == 0
               and got["max_logit_gap"] <= limit)
    checks = {"max_logit_gap": {"value": got["max_logit_gap"],
                                "limit": limit},
              "failed": {"value": run.failed, "limit": 0}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown()
    if control:
        out["control"] = got
    out["checks"] = checks
    return out


def _pct(xs, q):
    return percentile(xs, q) if xs else 0.0


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--requests-out", default=None,
                    help="write one JSON line of clocks per served request")
    a = ap.parse_args(argv)
    try:
        cell = spec.load_cell(a.workload)
        devices = chips(cell.chips)
    except (NoChip, KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out = measure(cell, a.seed, a.seconds, bool(a.trace), t_start,
                  devices=devices, requests_out=a.requests_out)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
