"""Pure-jnp oracles for the Pallas kernels (ground truth for allclose tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths):
    """E-Attention oracle: decode attention over paged KV.

    q:            (B, H, hd)        one query token per sequence
    k/v_pages:    (P, K, T, hd)     global paged KV slab, head-major (block
                                    size T)
    block_tables: (B, N) int32      physical block ids per sequence
    lengths:      (B,) int32        context length (tokens) per sequence
    Returns (B, H, hd).
    """
    B, H, hd = q.shape
    P, K, T, _ = k_pages.shape
    N = block_tables.shape[1]
    G = H // K

    k = k_pages[block_tables]  # (B, N, K, T, hd)
    v = v_pages[block_tables]
    k = k.transpose(0, 1, 3, 2, 4).reshape(B, N * T, K, hd)
    v = v.transpose(0, 1, 3, 2, 4).reshape(B, N * T, K, hd)

    qq = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qq, k, preferred_element_type=F32)
    s *= 1.0 / jnp.sqrt(jnp.array(hd, F32))
    pos = jnp.arange(N * T)[None, None, None, :]
    mask = pos < lengths[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bkgt,btkh->bkgh", p.astype(q.dtype), v)
    return o.reshape(B, H, hd)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """Full-sequence attention oracle with causal + sliding-window masking.

    q: (B, S, H, hd); k, v: (B, S, K, hd) (GQA: H = K * G). Returns q-shaped.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qq = q.reshape(B, S, K, G, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qq, k, preferred_element_type=F32)
    s *= 1.0 / jnp.sqrt(jnp.array(hd, F32))
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    o = jnp.einsum("bkgst,btkh->bskgh", p.astype(q.dtype), v)
    return o.reshape(B, S, H, hd)
