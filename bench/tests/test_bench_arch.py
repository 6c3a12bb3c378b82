"""Each configuration's weights, reference and shape check come from the
module its published `architectures[0]` names, `bench/arch/<name>.py`.

A second architecture, a dense decoder with attention bias (the registry's
codeqwen1.5-7b at its smoke widths), is added to a tiny checkout with new
files only and runs correct; the same cell with the bias left out of its
reference is not.  A configuration whose architecture has no module fails
before anything is served.  The dense module refuses a program of another
architecture, and draws and computes what the dense decoder's code did
before it moved there, bit for bit.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec
from bench.tests.tiny import TINY_CONFIG, bench_files, tiny_checkout

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"

QWEN_CONFIG = {
    "source": "https://huggingface.co/Qwen/CodeQwen1.5-7B",
    "repro_model": "codeqwen1.5-7b", "repro_smoke": True,
    "architectures": ["Qwen2ForCausalLM"],
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "num_hidden_layers": 2,
    "vocab_size": 512, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "torch_dtype": "bfloat16", "initializer_range": 0.02,
    "pool_mb": 64, "check": {"max_logit_gap": 0.01},
}


def _add_config(root: Path, name: str, config: dict) -> str:
    """Config `name` and its cell `<name>.cold` (the tiny traffic), with
    new files and entries only; returns the cell's name."""
    (root / f"bench/configs/{name}.json").write_text(json.dumps(config))
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": config["source"],
                         "file": f"bench/configs/{name}.json",
                         "reduced": ["num_hidden_layers"], "why": "CPU test"})
    b["workloads"].append({"name": f"{name}.cold", "config": name,
                           "traffic": "tiny", "chips": 1, "why": "CPU test"})
    for m in b["end_to_end"]:
        if "workloads" in m and "tiny.cold" in m["workloads"]:
            m["workloads"].append(f"{name}.cold")
    (root / "BENCHMARK.json").write_text(json.dumps(b, indent=1))
    return f"{name}.cold"


@pytest.fixture(scope="module")
def qwen_root(tmp_path_factory):
    root = tiny_checkout(tmp_path_factory.mktemp("checkout"), with_src=False)
    shutil.copy(DATA / "Qwen2ForCausalLM.py",
                root / "bench/arch/Qwen2ForCausalLM.py")
    _add_config(root, "tiny-qwen", QWEN_CONFIG)
    return root


@pytest.fixture
def qwen(qwen_root):
    return spec.load_cell("tiny-qwen.cold", qwen_root)


def _run(cell, seed=7):
    return harness.measure(cell, seed, 1.0, False, time.perf_counter(),
                           devices=jax.devices(), compile_cache=False)


def test_added_architecture_is_found_by_name_and_runs_correct(qwen_root,
                                                              qwen):
    before, after = bench_files(ROOT), bench_files(qwen_root)
    assert all(after[p] == data for p, data in before.items())
    assert Path(qwen.arch.__file__).name == "Qwen2ForCausalLM.py"
    assert {"bq", "bk", "bv"} <= set(qwen.arch.make(qwen.config, 7))
    out = _run(qwen)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"] == QWEN_CONFIG["check"]["max_logit_gap"]


def test_bias_left_out_of_the_reference_is_not_correct(qwen, monkeypatch):
    logits = qwen.arch.logits

    def no_bias(w, c, *a, **kw):
        w = {k: jnp.zeros_like(v) if k in ("bq", "bk", "bv") else v
             for k, v in w.items()}
        return logits(w, c, *a, **kw)

    monkeypatch.setattr(qwen.arch, "logits", no_bias)
    out = _run(qwen)
    assert out["failed"] == 0
    assert out["correct"] is False
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("architectures", [["NoSuchForCausalLM"], None])
def test_architecture_without_a_module_fails_before_serving(tmp_path,
                                                            architectures):
    root = tiny_checkout(tmp_path, with_src=False)
    config = dict(TINY_CONFIG)
    if architectures is None:
        del config["architectures"]
    else:
        config["architectures"] = architectures
    cell = _add_config(root, "tiny-other", config)
    with pytest.raises(FileNotFoundError if architectures else KeyError):
        spec.load_cell(cell, root)
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "3",
         "--seconds", "1", "--trace", "0"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert not [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert ("bench/arch/NoSuchForCausalLM.py" if architectures
            else "architectures") in r.stderr
    assert "no TPU" not in r.stderr  # it stopped before looking for chips


def _program_config(model: str):
    from repro.launch.serve import model_configs, parse_args

    args = parse_args(["--models", model, "--smoke", "--num-layers", "2"])
    return model_configs(args)[model]


@pytest.mark.parametrize("model, refused_for", [
    ("deepseek-7b", None),
    ("qwen3-moe-30b-a3b", "family"),  # sparse experts
    ("codeqwen1.5-7b", "qkv_bias"),  # attention bias
])
def test_dense_module_refuses_another_architecture(model, refused_for):
    cfg = _program_config(model)
    # the file has the program's sizes: only the architecture can differ
    c = dict(TINY_CONFIG, rope_theta=cfg.rope_theta)
    dense = spec.arch(c)
    if refused_for is None:
        dense.check_program(cfg, c)
    else:
        with pytest.raises(ValueError, match=refused_for):
            dense.check_program(cfg, c)


def _digest(named) -> str:
    h = hashlib.sha256()
    for name, a in named:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_dense_module_reproduces_the_golden_digest(seed):
    from repro.models import build_model

    golden = json.loads(
        (DATA / "LlamaForCausalLM.tiny.sha256.json").read_text())
    c = TINY_CONFIG
    dense = spec.arch(c)
    cfg = _program_config(c["repro_model"])
    expected = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    w = dense.make(c, seed)
    tree = dense.program_init_fn(c, seed, expected)()
    toks = np.random.default_rng(seed).integers(
        0, c["vocab_size"], (2, 24)).astype(np.int32)
    got = {
        "weights": _digest(sorted(w.items())),
        "program": _digest((jax.tree_util.keystr(p), a) for p, a in
                           jax.tree_util.tree_flatten_with_path(tree)[0]),
        "logits": _digest([("logits", dense.logits(w, c, toks, 16))]),
        "fp8_logits": _digest([("logits",
                                dense.logits(w, c, toks, 16, fp8=True))]),
    }
    assert got == golden["seeds"][str(seed)]
