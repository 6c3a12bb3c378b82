"""Find a cell's configuration, traffic and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, named after it:

  bench/configs/<config>.json    (the path BENCHMARK.json gives)
  bench/traffic/<traffic>.json
  bench/metrics/<metric>.py      a module with `read(run) -> float | None`

so a new cell, mix or metric is added with files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Optional

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable[..., Optional[float]]


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _reader(root: Path, name: str) -> Callable[..., Optional[float]]:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _metrics(root: Path, entries: list[dict], cell: str) -> tuple[Metric, ...]:
    return tuple(Metric(m["name"], m["unit"], _reader(root, m["name"]))
                 for m in entries
                 if "workloads" not in m or cell in m["workloads"])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell `name` of `root`/BENCHMARK.json with its files; raises
    KeyError for an unknown cell, FileNotFoundError for a missing file."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=_metrics(root, bench["end_to_end"], name),
        per_layer=_metrics(root, bench["per_layer"], name))
