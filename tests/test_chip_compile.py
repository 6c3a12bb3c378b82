"""Compile the served path's kernels for one described TPU v5e chip.

Interpret mode (every other kernel test) never checks Mosaic's tiling rules,
so a kernel can pass there and still be refused by the chip's compiler.
These tests lower and compile for a v5e that is described, not attached
(`jax.experimental.topologies`), with nothing run: they say the program
compiles and fits, nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and a test worker that describes it
at import would break collection on every other worker.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.models import build_model
from repro.serving import engine as engine_mod

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off around these."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("K,hd", [(32, 128), (4, 128), (8, 64)])
def test_paged_kernel_compiles(one_chip, K, hd):
    B, H, T, P, N = 4, 32, 16, 256, 64
    bf16 = jnp.bfloat16
    fn = jax.jit(lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                       interpret=False))
    compiled = fn.lower(_spec(one_chip, (B, H, hd), bf16),
                        _spec(one_chip, (P, K, T, hd), bf16),
                        _spec(one_chip, (P, K, T, hd), bf16),
                        _spec(one_chip, (B, N), jnp.int32),
                        _spec(one_chip, (B,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles(one_chip):
    # yi-9b prefill widths: 32 query heads over 4 KV heads, head_dim 128
    B, S, H, K, hd = 1, 1024, 32, 4, 128
    bf16 = jnp.bfloat16
    fn = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=False))
    compiled = fn.lower(_spec(one_chip, (B, S, H, hd), bf16),
                        _spec(one_chip, (B, S, K, hd), bf16),
                        _spec(one_chip, (B, S, K, hd), bf16)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The engine's whole decode step at deepseek-7b's published widths, cut
    to 8 of its 30 layers, with the kernel (not its interpreter) inside."""
    # the process sees only the CPU, so `auto` would pick interpret mode
    monkeypatch.setattr(kops, "_use_interpret", lambda mode: False)
    kops.paged_attention.clear_cache()
    engine_mod._paged_decode_step.clear_cache()
    try:
        cfg = dataclasses.replace(get_config("deepseek-7b"), num_layers=8)
        model = build_model(cfg)
        params = jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
        B, P, N, T = 4, 256, 64, 16
        pages = _spec(one_chip, (cfg.num_layers, P, cfg.num_kv_heads, T,
                                 cfg.resolved_head_dim), cfg.jnp_dtype)
        compiled = engine_mod._paged_decode_step.lower(
            params, cfg, _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B, N), jnp.int32),
            _spec(one_chip, (B,), jnp.int32), pages, pages,
            attn="kernel").compile()
    finally:
        kops.paged_attention.clear_cache()
        engine_mod._paged_decode_step.clear_cache()
    assert "tpu_custom_call" in compiled.as_text()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"decode step needs {used / 1e9:.2f} GB"


@pytest.mark.parametrize("S", [128, 512])
def test_prefill_forward_fits_one_chip(one_chip, S):
    """The served prefill's one program (`_prefill_forward`) at deepseek-7b's
    published widths, 8 of its 30 layers, at the benchmark's prompt lengths:
    the forward, the last-position gather and the cache cut into blocks."""
    cfg = dataclasses.replace(get_config("deepseek-7b"), num_layers=8)
    model = build_model(cfg)
    params = jax.tree.map(
        lambda s: _spec(one_chip, s.shape, s.dtype),
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))))
    T = 16
    compiled = engine_mod._prefill_forward.lower(
        params, {"tokens": _spec(one_chip, (1, S), jnp.int32)},
        _spec(one_chip, (1,), jnp.int32), model, cache_cap=S,
        block_tokens=T).compile()
    out = compiled.out_info
    assert out[0].shape == (1, cfg.vocab_size)
    assert [c.shape for c in out[1]] == [
        (8, 1, S // T, T, cfg.num_kv_heads, cfg.resolved_head_dim)] * 2
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, f"prefill needs {used / 1e9:.2f} GB"
