"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time,
per-program and per-kernel device time, and idle gaps by host activity.

Device planes are `/device:TPU:<i>`.  Their `XLA Ops` line holds one event
per operation run on the chip, and their `XLA Modules` line one event per
program run, named after the jitted function (`jit_<name>(<id>)`).  The
window is the host span `bench.window`, which the harness opens and closes
around the requests; the host spans inside it (`bench.idle`, `bench.serve`
and the layer calls of `harness._layer_spans`) name what the host was doing
while the device idled.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Optional

WINDOW = "bench.window"
KERNEL = "paged_attention"  # the Pallas kernel's op name on the device
CONTAINERS = ("while", "conditional", "call")
# the layer calls `harness._layer_spans` wraps
LAYER_SPANS = ("load", "start_instance", "prefill", "decode", "finish",
               "make_prefill_batch")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _program(name: str) -> str:
    """`jit__paged_decode_step(123)` -> `_paged_decode_step`."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # mean over the chips' planes
    programs: dict  # program name -> [calls, device seconds]
    ops: dict  # op label -> device seconds
    kernel_s: float  # device seconds of the paged-attention kernel
    kernel_calls: int
    idle_by_host: dict  # host span -> idle device seconds
    requests_traced: int = 0

    def per_call_ms(self, program: str) -> Optional[float]:
        calls, secs = self.programs.get(program, (0, 0.0))
        return 1e3 * secs / calls if calls else None

    def breakdown(self) -> dict:
        top = lambda d: [[k, v] for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops),
                "idle_gaps": top(self.idle_by_host)}


def reduce_file(path, *, chips=(0,)) -> Reduced:
    """The window's numbers from the trace at `path`, over the device
    planes of the chips with ids `chips`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host = [ev for p in pd.planes if p.name == "/host:CPU"
            for ln in p.lines for ev in ln.events]
    wins = [ev for ev in host if ev.name == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW} span in {path}")
    w0, w1 = wins[0].start_ns, wins[0].end_ns
    spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in host
             if (ev.name.startswith("bench.") and ev.name != WINDOW)
             or ev.name in LAYER_SPANS]

    busy, ops, programs = [], defaultdict(float), {}
    kernel_s, kernel_calls, planes = 0.0, 0, 0
    wanted = {f"/device:TPU:{i}" for i in chips}
    for p in pd.planes:
        if p.name not in wanted:
            continue
        planes += 1
        lines = {ln.name: ln for ln in p.lines}
        mods = []
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                if ev.start_ns < w0 or ev.end_ns > w1:
                    continue
                mods.append((ev.start_ns, ev.end_ns, _program(ev.name)))
                c = programs.setdefault(mods[-1][2], [0, 0.0])
                c[0] += 1
                c[1] += ev.duration_ns * 1e-9
        ivs, named = [], []
        if "XLA Ops" in lines:
            for ev in lines["XLA Ops"].events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    ivs.append((s, e))
                    named.append((s, e, op_name(ev.name)))
        named.sort()
        for (s, e, name), prog in zip(named, _innermost(
                [s for s, _, _ in named], mods)):
            if name == KERNEL:
                kernel_s += (e - s) * 1e-9
                kernel_calls += 1
            if name not in CONTAINERS:  # their body's ops are counted
                ops[f"{prog}/{name}"] += (e - s) * 1e-9
        busy.append(_union(ivs))
    if not planes:
        raise ValueError(f"no TPU device plane in {path}")

    idle = defaultdict(float)
    edges = [w0] + [x for iv in busy[0] for x in iv] + [w1]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    for (s, e), name in zip(gaps, _innermost([(s + e) // 2 for s, e in gaps],
                                             spans)):
        idle[name] += (e - s) * 1e-9
    busy_s = sum(sum(e - s for s, e in b) for b in busy) * 1e-9 / planes
    return Reduced((w1 - w0) * 1e-9, busy_s, programs, dict(ops), kernel_s,
                   kernel_calls, dict(idle))


def _innermost(points, spans) -> list[str]:
    """For each of the sorted `points`, the innermost of the nested host
    `spans` (start, end, name) that covers it, by one sweep."""
    spans = sorted(spans, key=lambda x: (x[0], -x[1]))
    out, stack, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][2] if stack else "other")
    return out


_HLO = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?: = |$)")


def op_name(event_name: str) -> str:
    """`%paged_attention.11 = bf16[...] custom-call(...)` -> `paged_attention`:
    the op's HLO name without its number."""
    m = _HLO.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def reduce_dir(d, run=None, **kw) -> Reduced:
    files = sorted(Path(d).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    red = reduce_file(files[-1], **kw)
    if run is not None:
        red.requests_traced = len(run.served)
    return red
