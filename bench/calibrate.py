"""Readings that set a cell's rate and its check's limit, on the chip.

  python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 \
      [--rates 1.0,2.0 --rate-seconds 20]

For each seed, one short run of the cell at its own load, checked against
the reference and against the fp8 control: one JSON line per seed with the
program's widest logit gap and the control's.  With `--rates`, first a sweep
on one server: a window at each rate, with how late the requests were taken
(the queue), to find the highest rate the server sustains.  Everything runs
in one process, which holds the chip.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

if __name__ == "__main__":
    _ROOT = Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(_ROOT / "src"), str(_ROOT)]

from bench import arrivals, harness, spec  # noqa: E402


def sweep(cell: spec.Cell, seed: int, rates, seconds: float, device):
    server, _ = harness.setup(cell, seed, device)
    try:
        for r in rates:
            t = dict(cell.traffic, rate_per_s=r)
            due = arrivals.schedule(t, seconds)
            served, wall = harness.window(server, due, seconds)
            q = [s.queue_s for s in served]
            svc = [s.end - s.start for s in served]
            print(json.dumps({
                "rate_per_s": r, "due": len(due), "served": len(served),
                "wall_s": wall, "service_mean_s": sum(svc) / len(svc),
                "queue_p50_s": harness._pct(q, 0.5),
                "queue_p95_s": harness._pct(q, 0.95), "queue_max_s": max(q),
                "queue_last_s": q[-1],
                "ttft_p50_s": harness._pct([s.ttft_s for s in served], 0.5),
                "ttft_p90_s": harness._pct([s.ttft_s for s in served], 0.9)}),
                flush=True)
    finally:
        server.close()


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate-seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    devices = harness.chips(cell.chips)
    harness.compile_cache_in_checkout()
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.rates:
        sweep(cell, seeds[0], [float(r) for r in a.rates.split(",")],
              a.rate_seconds, devices[0])
    for seed in seeds:
        out = harness.measure(cell, seed, a.seconds, False,
                              time.perf_counter(), devices=devices,
                              control=True, compile_cache=False)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"], **out["control"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
