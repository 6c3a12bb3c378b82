"""Arrival schedules for a traffic file.

The open-loop processes are copied from `repro.serverless.workload`
(`poisson_trace`, `burst_trace`): the benchmark keeps its own copy so that
no later change to the program can change the traffic it is measured with.

Every `--seed` of a cell sees the same arrival times, drawn from one seed
fixed here (`SCHEDULE_SEED`); `--seed` draws the weights and the prompts.
All requests of a mix have the same sizes, so the queue a run builds
depends on the server's speed and not on which seed the run drew.
"""
from __future__ import annotations

import random

# arrival times are the same for every --seed: a schedule redrawn per seed
# would move the queue, and so each TTFT percentile, far more than the
# server does between two runs of one seed
SCHEDULE_SEED = 1


def poisson(rate_per_s: float, seconds: float, rng: random.Random) -> list[float]:
    """Homogeneous Poisson arrivals (exponential gaps) in [0, seconds)."""
    out, t = [], 0.0
    while True:
        t += rng.expovariate(rate_per_s)
        if t >= seconds:
            return out
        out.append(t)


def burst(rate_per_s: float, seconds: float, rng: random.Random, *,
          burst_every_s: float, burst_size: int,
          burst_window_s: float) -> list[float]:
    """Poisson background plus a volley of `burst_size` arrivals inside
    `burst_window_s` every `burst_every_s` seconds."""
    out = poisson(rate_per_s, seconds, rng)
    t = burst_every_s
    while t < seconds:
        out += [t + rng.uniform(0.0, burst_window_s) for _ in range(burst_size)]
        t += burst_every_s
    return sorted(x for x in out if x < seconds)


def schedule(traffic: dict, seconds: float) -> list[float]:
    """Due times, in seconds after the window opens, for `traffic`."""
    rng = random.Random(SCHEDULE_SEED)
    kind = traffic["arrival"]
    if kind == "poisson":
        return poisson(traffic["rate_per_s"], seconds, rng)
    if kind == "burst":
        return burst(traffic["rate_per_s"], seconds, rng,
                     burst_every_s=traffic["burst_every_s"],
                     burst_size=traffic["burst_size"],
                     burst_window_s=traffic["burst_window_s"])
    raise ValueError(f"unknown arrival process {kind!r}")
