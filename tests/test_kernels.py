"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels execute in interpret mode (the kernel body runs exactly as written,
including BlockSpec tiling and scalar prefetch) — see kernels/ops.py.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import flash_attention_ref, paged_attention_ref

KEY = jax.random.PRNGKey(42)


def tol_for(dtype):
    return {"float32": 2e-5, "bfloat16": 2e-2}[jnp.dtype(dtype).name]


# ------------------------------------------------------------ paged attention
PAGED_CASES = [
    # (B, H, K, hd, block_T, pages, table_N)
    (1, 4, 4, 64, 16, 16, 4),      # MHA
    (4, 8, 2, 64, 16, 64, 6),      # GQA 4:1
    (2, 16, 1, 128, 32, 16, 4),    # MQA (recurrentgemma-style)
    (3, 32, 4, 128, 16, 32, 8),    # qwen3-moe heads
    (2, 8, 8, 128, 64, 8, 2),      # large blocks
]


@pytest.mark.parametrize("B,H,K,hd,T,P,N", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_matches_ref(B, H, K, hd, T, P, N, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, H, K, hd, T)) & 0x7FFFFFFF), 5)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    k_pages = jax.random.normal(ks[1], (P, K, T, hd), jnp.float32).astype(dtype)
    v_pages = jax.random.normal(ks[2], (P, K, T, hd), jnp.float32).astype(dtype)
    tables = jax.random.randint(ks[3], (B, N), 0, P, dtype=jnp.int32)
    max_len = N * T
    lengths = jax.random.randint(ks[4], (B,), 1, max_len + 1, dtype=jnp.int32)
    out = paged_attention(q, k_pages, v_pages, tables, lengths)
    ref = paged_attention_ref(q, k_pages, v_pages, tables, lengths)
    assert out.shape == ref.shape == (B, H, hd)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < tol_for(dtype), f"err {err}"


def test_paged_attention_single_token_context():
    """length=1: exactly one KV slot contributes."""
    q = jnp.ones((1, 2, 64))
    k_pages = jax.random.normal(KEY, (4, 2, 16, 64))
    v_pages = jax.random.normal(jax.random.fold_in(KEY, 1), (4, 2, 16, 64))
    tables = jnp.array([[2, 0]], jnp.int32)
    lengths = jnp.array([1], jnp.int32)
    out = paged_attention(q, k_pages, v_pages, tables, lengths)
    expect = v_pages[2, :, 0]  # softmax over one position = that position's V
    assert jnp.allclose(out[0], expect, atol=1e-5)


def test_paged_attention_ignores_stale_pages():
    """Entries past `length` (and their page ids) must not affect output."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 4, 64))
    k_pages = jax.random.normal(ks[1], (8, 2, 16, 64))
    v_pages = jax.random.normal(ks[2], (8, 2, 16, 64))
    t1 = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    t2 = jnp.array([[0, 1, 7], [3, 4, 6]], jnp.int32)  # tails differ
    lengths = jnp.array([20, 30], jnp.int32)  # only first 2 blocks live
    o1 = paged_attention(q, k_pages, v_pages, t1, lengths)
    o2 = paged_attention(q, k_pages, v_pages, t2, lengths)
    assert jnp.allclose(o1, o2, atol=1e-6)


# ------------------------------------------------------------ flash attention
FLASH_CASES = [
    # (B, S, H, K, hd, causal, window, bq, bk)
    (2, 256, 4, 2, 64, True, 0, 64, 64),
    (2, 256, 4, 2, 64, True, 100, 64, 64),   # SWA, non-block-aligned window
    (1, 128, 8, 1, 32, False, 0, 32, 64),    # bidirectional (whisper encoder)
    (2, 512, 2, 2, 64, True, 64, 128, 128),  # window smaller than block
    (1, 256, 16, 1, 128, True, 0, 128, 64),  # MQA, rectangular blocks
]


@pytest.mark.parametrize("B,S,H,K,hd,causal,window,bq,bk", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(B, S, H, K, hd, causal, window, bq, bk, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, hash((B, S, H, K, hd)) & 0x7FFFFFFF), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    assert err < tol_for(dtype), f"err {err}"


def test_flash_block_size_invariance():
    """Same result regardless of tiling choice."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    outs = [flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
            for bq, bk in [(32, 32), (64, 128), (256, 256)]]
    for o in outs[1:]:
        assert jnp.allclose(outs[0], o, atol=1e-5)


def test_flash_matches_model_attention():
    """The kernel agrees with the model stack's dense attention path."""
    from repro.models.common import attention_dense

    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 64))
    k = jax.random.normal(ks[1], (2, 128, 2, 64))
    v = jax.random.normal(ks[2], (2, 128, 2, 64))
    out = flash_attention(q, k, v, causal=True, window=48, block_q=64, block_k=64)
    ref = attention_dense(q, k, v, causal=True, window=48)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
