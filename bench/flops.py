"""Operations and bytes the algorithm needs, from shapes alone.

`c` is a configuration file (published key names); `arch` its
architecture module (`bench/arch/`), which counts the work of each layer's
feed-forward.  `n` is the number of tokens a decode step attends over: the
context before the step plus the token it adds.  Batch 1: the served path
decodes one sequence per step.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.peaks import Peak


def attn_dims(c: dict) -> tuple[int, int, int, int, int, int]:
    """(L, D, H, K, hd, V) of a configuration file: the attention's and
    the head's sizes, by their published names."""
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["vocab_size"])


def itemsize(c: dict) -> int:
    return jnp.dtype(c["torch_dtype"]).itemsize


def decode_step_flops(c: dict, n: int, arch) -> int:
    """Model FLOPs of one decode step: per layer the q/k/v and output
    projections, attention over `n` tokens (q.k and p.v) and the
    feed-forward as `arch.ffn_flops` counts it; then the output head.
    Embedding lookup and norms are not counted (no multiply-adds of
    note)."""
    L, D, H, K, hd, V = attn_dims(c)
    proj = 2 * D * (H + 2 * K) * hd + 2 * H * hd * D
    attn = 4 * H * hd * n
    return L * (proj + attn) + sum(arch.ffn_flops(c)) + 2 * D * V


def paged_attn_flops(c: dict, n: int) -> int:
    """One call of the paged kernel (one layer, one sequence)."""
    _, _, H, _, hd, _ = attn_dims(c)
    return 4 * H * hd * n


def paged_attn_bytes(c: dict, n: int) -> int:
    """HBM bytes one kernel call needs: K and V of `n` tokens, q in, o out."""
    _, _, H, K, hd, _ = attn_dims(c)
    return (2 * n * K * hd + 2 * H * hd) * itemsize(c)


def least_seconds(flops: float, nbytes: float, p: Peak) -> tuple[float, str]:
    """The chip's least time for the work, and which peak bounds it."""
    t_c, t_m = flops / p.bf16_flops, nbytes / p.hbm_bytes_per_s
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
