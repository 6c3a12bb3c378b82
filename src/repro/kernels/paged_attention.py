"""E-Attention as a TPU Pallas kernel: paged decode attention.

TPU adaptation of the paper's PagedAttention-derived CUDA kernel
(`segmented_attention`): instead of threads chasing physical addresses, the
block table is a *scalar-prefetch* operand whose entries drive the BlockSpec
index_map — each KV block is DMA'd HBM->VMEM exactly when its grid step runs.
That is the TPU-native analogue of physical-address access at block
granularity (DESIGN.md §2).

Layout:
  q            (B, K, G, hd)   G = H/K grouped queries per kv head
  k/v_pages    (P, K, T, hd)   the pool's KV slab, head-major, block size T
                               tokens (the page's last two dims are the
                               (T, hd) tile Mosaic DMAs per grid step)
  block_tables (B, N) int32    physical block ids (scalar-prefetched)
  lengths      (B,) int32      live context per sequence (scalar-prefetched)

Grid (B, K, N): online softmax accumulates across the block axis in VMEM
scratch; the output is written on the final block.  Blocks past a sequence's
length are skipped with pl.when (no MXU work; the DMA index is clamped to a
valid page).  hd and T should be multiples of 128/8 for MXU/VREG alignment —
all assigned configs satisfy this.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = float("-inf")


def _kernel(tables_ref, lengths_ref,  # scalar prefetch
            q_ref, k_ref, v_ref,  # VMEM inputs
            o_ref,  # VMEM output
            m_scr, l_scr, acc_scr):  # VMEM scratch
    b = pl.program_id(0)
    i = pl.program_id(2)
    n_blocks = pl.num_programs(2)
    block_T = k_ref.shape[0]

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    block_start = i * block_T

    @pl.when(block_start < length)
    def _compute():
        q = q_ref[...].astype(F32)  # (G, hd); None dims are squeezed
        k = k_ref[...].astype(F32)  # (T, hd)
        v = v_ref[...].astype(F32)  # (T, hd)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale  # (G, T)
        token_pos = block_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(token_pos < length, s, NEG_INF)

        m_prev = m_scr[...]  # (G, 1)
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (G, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (G, T); masked entries exp(-inf)=0
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)  # (G, hd)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret: bool = True):
    """q: (B, H, hd) -> (B, H, hd). See module docstring for page layout."""
    B, H, hd = q.shape
    P, K, T, _ = k_pages.shape
    N = block_tables.shape[1]
    G = H // K
    assert H % K == 0

    qg = q.reshape(B, K, G, hd)

    def q_map(b, k, i, tables, lengths):
        return (b, k, 0, 0)

    def kv_map(b, k, i, tables, lengths):
        # clamp: blocks past length still need a *valid* page id for the DMA
        return (tables[b, i], k, 0, 0)

    def o_map(b, k, i, tables, lengths):
        return (b, k, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, N),
        in_specs=[
            pl.BlockSpec((None, None, G, hd), q_map),
            pl.BlockSpec((None, None, T, hd), kv_map),
            pl.BlockSpec((None, None, T, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, G, hd), o_map),
        scratch_shapes=[
            pltpu.VMEM((G, 1), F32),
            pltpu.VMEM((G, 1), F32),
            pltpu.VMEM((G, hd), F32),
        ],
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, qg, k_pages, v_pages)
    return out.reshape(B, H, hd)
