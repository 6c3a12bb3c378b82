"""Launcher entrypoints run end-to-end in subprocesses (CLI contract)."""
import os
import subprocess
import sys
import textwrap

import pytest

ENV = {**os.environ, "PYTHONPATH": "src"}


@pytest.mark.parametrize("args", [
    ["-m", "repro.launch.train", "--arch", "llama3.2-1b", "--smoke",
     "--steps", "4", "--seq-len", "32", "--batch", "2"],
])
def test_train_launcher(args):
    out = subprocess.run([sys.executable] + args, capture_output=True,
                         text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "loss" in out.stdout


def test_train_launcher_resume(tmp_path):
    base = ["-m", "repro.launch.train", "--arch", "yi-9b", "--smoke",
            "--steps", "6", "--seq-len", "32", "--batch", "2",
            "--ckpt-every", "3", "--ckpt-dir", str(tmp_path)]
    out1 = subprocess.run([sys.executable] + base, capture_output=True,
                          text=True, timeout=600, env=ENV)
    assert out1.returncode == 0, out1.stderr[-1500:]
    # relaunch with more steps: must resume from the saved step, not step 0
    args2 = list(base)
    args2[args2.index("--steps") + 1] = "8"
    out2 = subprocess.run([sys.executable] + args2, capture_output=True,
                          text=True, timeout=600, env=ENV)
    assert out2.returncode == 0, out2.stderr[-1500:]
    assert "resumed from step 6" in out2.stdout


def test_serve_launcher():
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--models", "llama3.2-1b", "--requests", "2",
         "--prompt-len", "16", "--gen-tokens", "4"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "reuse=100%" in out.stdout  # second request fully reused


def test_serve_launcher_trace_replay():
    """--trace replays a synthesized serverless workload through the
    control-plane Gateway (DESIGN.md §13): lifecycle-classified requests
    plus a cold-rate/percentile summary from the metrics sink."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--models", "llama3.2-1b", "--trace", "poisson", "--requests", "3",
         "--keep-alive-policy", "adaptive", "--mean-interarrival", "5",
         "--prompt-len", "16", "--gen-tokens", "2"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "serverless summary:" in out.stdout
    assert "cold" in out.stdout and "warm" in out.stdout  # keep-alive hit
    assert "policy=adaptive trace=poisson" in out.stdout


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serve_launcher_depth_cut():
    """--num-layers cuts every served model's depth and says so."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve",
         "--models", "llama3.2-1b", "--num-layers", "1", "--requests", "2",
         "--prompt-len", "16", "--gen-tokens", "2"],
        capture_output=True, text=True, timeout=600, env=ENV)
    assert out.returncode == 0, out.stderr[-1500:]
    assert "reduced: llama3.2-1b num_layers 4 -> 1" in out.stdout
    assert "reuse=100%" in out.stdout


def test_chip_smoke_refuses_without_tpu(tmp_path):
    """No TPU: non-zero exit and no result line — from the repo root, and
    from a directory that holds chip_smoke.py and nothing else."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(ROOT, "chip_smoke.py")).read())
    for script, cwd in [(os.path.join(ROOT, "chip_smoke.py"), ROOT),
                        (str(alone), str(tmp_path))]:
        out = subprocess.run([sys.executable, script], capture_output=True,
                             text=True, timeout=600, env=env, cwd=cwd)
        assert out.returncode != 0
        assert '"ok"' not in out.stdout
        assert "FAIL" in out.stderr


CACHE_SNIPPET = textwrap.dedent("""
    import jax
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    print("DIR", use_compile_cache(), jax.config.jax_compilation_cache_dir,
          REPO_CACHE_DIR)
""")


@pytest.mark.parametrize("placed", [None, "outside"])
def test_compile_cache_placement(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits at
    the fixed <repo>/.jax_cache."""
    env = {k: v for k, v in ENV.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / placed)
    out = subprocess.run([sys.executable, "-c", CACHE_SNIPPET],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-1500:]
    used, config_dir, repo_dir = out.stdout.split("DIR")[1].split()
    expect = str(tmp_path / placed) if placed else os.path.join(ROOT,
                                                                ".jax_cache")
    assert used == config_dir == expect
    assert repo_dir == os.path.join(ROOT, ".jax_cache")


FLEET_SNIPPET = textwrap.dedent("""
    import jax
    import chip_smoke
    from repro.launch import serve

    argv = ["--models", "llama3.2-1b,yi-9b", "--num-layers", "2",
            "--trace", "poisson", "--requests", "6", "--prompt-len", "16",
            "--gen-tokens", "2", "--mean-interarrival", "0.5"]
    assert len(jax.devices()) == 4
    print("PROBLEMS", chip_smoke.fleet_phase(serve, argv, jax.devices()))
""")


def test_fleet_one_engine_per_device():
    """chip_smoke's four-chip phase, rehearsed on four CPU devices: each of
    four engines keeps its tensors and KV slab on its own device, at least
    two serve, and every request's tokens equal a one-engine replay."""
    env = {**ENV, "PYTHONPATH": os.pathsep.join(["src", ROOT]),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", FLEET_SNIPPET],
                         capture_output=True, text=True, timeout=600, env=env,
                         cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PROBLEMS []" in out.stdout, out.stdout[-2000:]
