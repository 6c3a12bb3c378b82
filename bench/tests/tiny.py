"""A checkout with one tiny cell more, for tests on the CPU.

`tiny_checkout(dst)` copies BENCHMARK.json, bench/ and src/ to `dst` and
adds, with new files and entries only, the configuration `tiny` (the
registry's deepseek-7b at its smoke widths, 2 layers), the traffic `tiny`
and the cell `tiny.cold`.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_CONFIG = {
    "source": "https://huggingface.co/deepseek-ai/deepseek-llm-7b-base",
    "repro_model": "deepseek-7b", "repro_smoke": True,
    "architectures": ["LlamaForCausalLM"],
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "torch_dtype": "bfloat16", "initializer_range": 0.02,
    "pool_mb": 64, "check": {"max_logit_gap": 0.01},
}

TINY_TRAFFIC = {"arrival": "poisson", "rate_per_s": 4.0,
                "prompt_len": 16, "gen_tokens": 4, "keep_alive": "zero"}


def bench_files(root: Path) -> dict:
    """Every file under `root`/bench, by its path, with its bytes."""
    return {p.relative_to(root): p.read_bytes()
            for p in (root / "bench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def tiny_checkout(dst: Path, *, with_src: bool = True) -> Path:
    dst = Path(dst)
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    ignore = shutil.ignore_patterns("__pycache__", ".bench_*")
    shutil.copytree(ROOT / "bench", dst / "bench", ignore=ignore)
    if with_src:
        shutil.copytree(ROOT / "src", dst / "src", ignore=ignore)
    (dst / "bench/configs/tiny.json").write_text(json.dumps(TINY_CONFIG))
    (dst / "bench/traffic/tiny.json").write_text(json.dumps(TINY_TRAFFIC))
    b = json.loads((dst / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": TINY_CONFIG["source"],
                         "file": "bench/configs/tiny.json",
                         "reduced": ["num_hidden_layers"], "why": "CPU test"})
    b["workloads"].append({"name": "tiny.cold", "config": "tiny",
                           "traffic": "tiny", "chips": 1, "why": "CPU test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and "deepseek-7b-l8.cold" in m["workloads"]:
            m["workloads"].append("tiny.cold")
    (dst / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return dst
