"""A configuration, a traffic mix, a metric and a cell are added with new
files and entries only, and the harness finds them by name; a run without a
TPU prints no result line."""
import json
import os
import subprocess
import sys
from pathlib import Path

from bench import spec
from bench.tests.tiny import tiny_checkout

ROOT = Path(__file__).resolve().parents[2]


def _files(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_added_files_are_found_by_name(tmp_path):
    tiny_checkout(tmp_path, with_src=False)
    (tmp_path / "bench/metrics/tiny.requests.py").write_text(
        "def read(run):\n    return float(len(run.served))\n")
    b = json.loads((tmp_path / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "tiny.requests", "unit": "1",
                           "better": "higher", "source": "host_clock",
                           "layer": "gateway", "moves": "ttft_p50_s",
                           "workloads": ["tiny.cold"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    # every file the benchmark had is there, byte for byte
    before, after = _files(ROOT), _files(tmp_path)
    assert all(after[p] == data for p, data in before.items())

    cell = spec.load_cell("tiny.cold", tmp_path)
    assert cell.config["hidden_size"] == 128
    assert cell.traffic["prompt_len"] == 16
    names = [m.name for m in cell.per_layer]
    assert "tiny.requests" in names
    assert "decode.step_ms" not in names  # listed for other cells only
    assert [m.name for m in cell.end_to_end] == ["ttft_p50_s", "setup_s"]
    m = next(m for m in cell.per_layer if m.name == "tiny.requests")
    assert m.read(type("R", (), {"served": [1, 2, 3]})()) == 3.0
    # the cells of the benchmark itself are untouched
    assert spec.load_cell("yi-9b-l8.chat", tmp_path).config["vocab_size"] == 64000


def _run(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--seed", "3", "--seconds", "1",
         "--trace", "0", *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _result_lines(stdout: str):
    return [ln for ln in stdout.splitlines() if ln.startswith("{")]


def test_run_without_tpu_prints_no_result(tmp_path):
    tiny_checkout(tmp_path, with_src=False)
    r = _run(tmp_path, "--workload", "tiny.cold")
    assert r.returncode != 0
    assert not _result_lines(r.stdout)
    assert "no TPU" in r.stderr


def test_unknown_cell_prints_no_result(tmp_path):
    tiny_checkout(tmp_path, with_src=False)
    r = _run(tmp_path, "--workload", "no-such-cell")
    assert r.returncode != 0
    assert not _result_lines(r.stdout)
