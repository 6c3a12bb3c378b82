"""Unified metrics registry: named counters.

The repo's observable surfaces grew counters ad hoc — bare attributes on
stores, hand-assembled ``summary()`` dicts, per-benchmark percentile math.
The registry is the one sink they can all feed: get-or-create named
counters, count, and read back a ``repro.stats``-style typed snapshot
whose key set cannot drift from the counter names.

Thread-safe (the prefetch worker counts promotions while a request thread
counts loads); cheap enough for per-request paths (one dict lookup + one
locked add per observation).  ``percentile`` here is the ONE index
convention every plane reports with — ``core.trace`` re-exports it, so the
sim's summaries and the serverless sink agree.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Sequence

from repro.stats import Snapshot


def percentile(xs: Sequence[float], q: float) -> float:
    """The ONE percentile index convention every plane reports with:
    sorted values, index ``min(n - 1, int(n * q))``, 0.0 on empty input.
    ``core.cluster.summarize`` and the serverless ``MetricsSink`` both
    route through here (via ``core.trace``), so fig8/fig16 percentiles
    cannot drift apart (tests/test_serverless.py pins the convention)."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(len(xs) * q))]


class Counter:
    """Monotone named count."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str, lock: threading.Lock):
        self.name = name
        self._value = 0
        self._lock = lock

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


@dataclass(frozen=True)
class MetricsStats(Snapshot):
    """Typed registry snapshot (repro.stats convention): counter name ->
    value."""

    counters: dict = None  # type: ignore[assignment]


class MetricsRegistry:
    """Get-or-create named instruments + one typed snapshot of them all."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name, self._lock)
        return c

    def absorb(self, counts: dict, *, prefix: str = "") -> None:
        """Fold a legacy counter dict (``fault_summary()``, ``summary()``)
        into named counters — the migration path off scattered dicts."""
        for k, v in counts.items():
            if isinstance(v, dict):
                self.absorb(v, prefix=f"{prefix}{k}.")
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                self.counter(f"{prefix}{k}").inc(int(v))

    def snapshot(self) -> MetricsStats:
        with self._lock:
            counters = {n: c.value for n, c in sorted(self._counters.items())}
        return MetricsStats(counters=counters)
