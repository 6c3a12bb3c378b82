"""Config registry: exact assigned hyperparameters, param counts in range,
cell enumeration (40 total = 33 runnable + 7 documented skips).

Also hosts the CI-subset drift guard: the fast-test list scripts/ci.sh runs
is asserted against the actual contents of tests/, so a new test module
cannot silently fall out of `make test-fast`.
"""
from pathlib import Path

import pytest

from repro.configs import SHAPES, all_configs, get_config, runnable_cells, skipped_cells

# Test modules deliberately EXCLUDED from the fast subset: jax compile
# subprocesses, kernel/model numerics, or multi-second engine paths.  A new
# test module must be added either to tests/fast_tests.txt (so scripts/ci.sh
# runs it) or here (with a reason); test_fast_subset_tracks_tests_directory
# fails otherwise — the old hand-listed subset in ci.sh drifted silently.
SLOW_TESTS = {
    "tests/test_chip_compile.py",  # compiles for a described TPU v5e
    "tests/test_compress.py",      # jitted compression numerics
    "tests/test_distributed.py",   # sharding/mesh compile subprocesses
    "tests/test_engine.py",        # full engine decode compiles
    "tests/test_fastpath.py",      # engine load/decode equivalence (jit)
    "tests/test_kernels.py",       # Pallas kernel numerics
    "tests/test_launchers.py",     # launch subprocesses
    "tests/test_migration.py",     # cross-engine decode handoff (jit)
    "tests/test_models.py",        # per-arch forward numerics
    "tests/test_obs_live.py",      # real-plane spans, JAX compile listeners
    "tests/test_roofline.py",      # analysis over real configs
    "tests/test_system.py",        # end-to-end serve scenarios
    "tests/test_train.py",         # training-step compiles
}


def test_fast_subset_tracks_tests_directory():
    root = Path(__file__).resolve().parent
    listed = {line.strip() for line in
              (root / "fast_tests.txt").read_text().splitlines()
              if line.strip() and not line.lstrip().startswith("#")}
    actual = {f"tests/{p.name}" for p in root.glob("test_*.py")}
    missing_files = listed - actual
    assert not missing_files, f"fast_tests.txt lists absent modules: {missing_files}"
    assert not (listed & SLOW_TESTS), "a module is both fast and slow"
    uncovered = actual - listed - SLOW_TESTS
    assert not uncovered, (
        f"test modules in neither tests/fast_tests.txt nor SLOW_TESTS "
        f"(they would silently skip CI's fast gate): {uncovered}")

EXPECT = {
    # name: (layers, d_model, heads, kv, d_ff, vocab)
    "qwen3-moe-30b-a3b": (48, 2048, 32, 4, None, 151936),
    "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
    "deepseek-7b": (30, 4096, 32, 32, 11008, 102400),
    "codeqwen1.5-7b": (32, 4096, 32, 32, 13440, 92416),
    "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
    "yi-9b": (48, 4096, 32, 4, 11008, 64000),
    "mamba2-2.7b": (64, 2560, None, None, None, 50280),
    "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
    "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
    "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
}

PARAM_RANGE = {  # billions, generous bounds
    "qwen3-moe-30b-a3b": (25, 35), "mixtral-8x7b": (42, 50),
    "deepseek-7b": (6, 8), "codeqwen1.5-7b": (6.5, 9),
    "llama3.2-1b": (1.0, 1.5), "yi-9b": (8, 10), "mamba2-2.7b": (2.4, 3.2),
    "whisper-tiny": (0.02, 0.08), "qwen2-vl-7b": (6.5, 9),
    "recurrentgemma-9b": (5.5, 11),
}


def test_all_ten_archs_registered():
    assert len(all_configs()) == 10


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_exact_assigned_hyperparams(name):
    cfg = get_config(name)
    L, d, h, kv, ff, vocab = EXPECT[name]
    assert cfg.num_layers == L and cfg.d_model == d and cfg.vocab_size == vocab
    if h is not None and cfg.family != "ssm":
        assert cfg.num_heads == h and cfg.num_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff


@pytest.mark.parametrize("name", sorted(PARAM_RANGE))
def test_param_counts_in_published_range(name):
    lo, hi = PARAM_RANGE[name]
    count = get_config(name).param_count() / 1e9
    assert lo <= count <= hi, f"{name}: {count:.2f}B not in [{lo}, {hi}]"


def test_moe_details():
    q3 = get_config("qwen3-moe-30b-a3b")
    assert q3.num_experts == 128 and q3.experts_per_token == 8
    assert q3.moe_d_ff == 768
    mx = get_config("mixtral-8x7b")
    assert mx.num_experts == 8 and mx.experts_per_token == 2
    assert mx.sliding_window == 4096


def test_cell_matrix_is_complete():
    run = runnable_cells()
    skip = skipped_cells()
    assert len(run) + len(skip) == 10 * 4 == 40
    assert len(run) == 33
    # long_500k runs exactly for the sub-quadratic archs
    long_runs = {a for a, s in run if s == "long_500k"}
    assert long_runs == {"mamba2-2.7b", "mixtral-8x7b", "recurrentgemma-9b"}


def test_segments_cover_pattern():
    for name, cfg in all_configs().items():
        rebuilt = []
        for unit, rep in cfg.segments:
            rebuilt.extend(unit * rep)
        assert tuple(rebuilt) == cfg.pattern, name


def test_recurrentgemma_pattern():
    cfg = get_config("recurrentgemma-9b")
    assert len(cfg.pattern) == 38
    assert cfg.pattern.count("swa") == 12 and cfg.pattern.count("rglru") == 26


def test_smoke_configs_are_small():
    for name, cfg in all_configs().items():
        s = cfg.smoke()
        assert s.d_model <= 128 and s.vocab_size <= 512 and s.num_layers <= 4
        assert s.family == cfg.family


def test_padded_vocab():
    assert get_config("mamba2-2.7b").padded_vocab == 50432
    assert get_config("whisper-tiny").padded_vocab == 51968
    assert get_config("yi-9b").padded_vocab == 64000
