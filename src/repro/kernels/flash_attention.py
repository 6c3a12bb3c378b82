"""Causal / sliding-window flash attention as a TPU Pallas kernel (prefill).

Online-softmax blockwise attention with GQA, used for long-context prefill.
Grid (B, H, nq, nk) with the KV axis innermost; VMEM scratch carries the
(m, l, acc) running state across KV blocks.  Fully-masked KV blocks are
skipped with pl.when *before* any DMA-dependent compute executes — for causal
attention this halves the MXU work; for sliding-window attention it bounds
work per q block to O(window).

Block sizes default to (128, 128): MXU-aligned (multiples of 8 sublanes x 128
lanes) and small enough that q/k/v/acc tiles fit VMEM comfortably
(3 * 128 * hd * 4B + scratch << 16 MiB for hd <= 256).
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


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, block_q: int, block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    should_run = True
    if causal:
        # skip blocks entirely in the future
        should_run = k_start <= q_start + block_q - 1
    if window > 0:
        # skip blocks entirely behind the window
        should_run = jnp.logical_and(
            should_run, k_start + block_k - 1 > q_start - window)

    @pl.when(should_run)
    def _compute():
        q = q_ref[...].astype(F32)  # (block_q, hd)
        k = k_ref[...].astype(F32)  # (block_k, hd)
        v = v_ref[...].astype(F32)
        scale = 1.0 / math.sqrt(q.shape[-1])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kp <= qp
        if window > 0:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # rows with everything masked so far: keep exp well-defined
        corr = jnp.where(jnp.isinf(m_new), 0.0, jnp.exp(m_prev - m_new))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=F32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True):
    """q: (B, S, H, hd); k, v: (B, S, K, hd). Returns (B, S, H, hd).

    The kernel runs head-major, (B, H, S, hd): each block's last two dims
    are then the (block, hd) tile Mosaic requires, which a squeezed head
    axis in second-to-last place is not."""
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    assert H % K == 0
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))

    grid = (B, H, nq, nk)
    kernel = functools.partial(_kernel, causal=causal, window=window,
                               block_q=block_q, block_k=block_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, block_k, hd),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, 1), F32),
            pltpu.VMEM((block_q, hd), F32),
        ],
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(qh, kh, vh)
    return out.transpose(0, 2, 1, 3)
