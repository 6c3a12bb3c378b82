"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage:
    PYTHONPATH=src python -m benchmarks.run [--only fig8,fig10,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

SUITES = [
    ("fig4", "benchmarks.fig4_locality", "Fig 4a access-interval locality"),
    ("fig8", "benchmarks.fig8_ttft", "Fig 2/8 TTFT breakdown per approach"),
    ("table1", "benchmarks.table1_decode", "Table 1 decode throughput / ODKV overhead"),
    ("fig9", "benchmarks.fig9_breakdown", "Fig 9 +Reuse/+ODKV vs batch"),
    ("fig10", "benchmarks.fig10_alloc", "Fig 10 allocation policies"),
    ("fig11", "benchmarks.fig11_odkv", "Fig 11 ODKV space + overhead"),
    ("fig12", "benchmarks.fig12_sensitivity", "Fig 12 locality/pool sensitivity"),
    ("fig13", "benchmarks.fig13_multigpu", "Fig 13 multi-GPU P99 scaling"),
    ("fig14", "benchmarks.fig14_concurrency",
     "Fig 14 concurrent multi-instance workers + queueing-aware affinity"),
    ("fig15", "benchmarks.fig15_fastpath",
     "Fig 15 data-plane fast-path load / sync-free decode / indexed sim"),
    ("fig16", "benchmarks.fig16_serverless",
     "Fig 16 serverless control plane: keep-alive x pressure x arrivals"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite keys (e.g. fig8,fig10)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    failures = 0
    for key, module, desc in SUITES:
        if only and key not in only:
            continue
        print(f"# === {key}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            mod.run()
            print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {key} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
