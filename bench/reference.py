"""Plain float32 reference of the dense decoder block, and its control.

A straightforward `jax.numpy` forward of the published architecture
(Llama-style, as DeepSeek LLM and Yi publish it): RMSNorm, rotary
embedding on halves of each head, causal grouped-query attention with a
1/sqrt(head_dim) scale, a SwiGLU MLP, a final RMSNorm and an untied output
head.  Every matmul runs in float32 at `Precision.HIGHEST`.  It shares no
code with the program: it reads only the configuration file and the arrays
of `bench.weights.make`.

`precision="fp8"` is the control: the same forward with every matmul's two
operands rounded to float8 e4m3 (per row of the activations, per output
column of the weights), the precision step below the served bfloat16.

The forward runs one layer at a time over a batch of whole sequences and
unembeds only the positions that are compared.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = float(jnp.finfo(FP8).max)


def _q8(x, axis):
    """Round `x` to e4m3 with one scale per slice along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(FP8).astype(F32) * s


def _mm(spec, a, w, fp8, a_axes=(-1,), w_axes=(0,)):
    """einsum in float32 at full precision.  Under fp8 both operands are
    first rounded, with one scale per slice over their contracted axes."""
    a = a.astype(F32)
    w = w.astype(F32)
    if fp8:
        a, w = _q8(a, a_axes), _q8(w, w_axes)
    return jnp.einsum(spec, a, w, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = pos[:, :, None].astype(F32) * inv  # (B, S, hd/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _layer(x, lw, *, eps, theta, fp8):
    B, S, D = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _rms(x, 1.0 + lw["ln1_delta"], eps)
    q = _rope(_mm("bsd,dhk->bshk", h, lw["wq"], fp8), pos, theta)
    k = _rope(_mm("bsd,dhk->bshk", h, lw["wk"], fp8), pos, theta)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], fp8)
    H, K, hd = q.shape[2], k.shape[2], q.shape[3]
    q = q.reshape(B, S, K, H // K, hd)
    s = _mm("bskgd,btkd->bkgst", q, k, fp8, w_axes=(3,)) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm("bkgst,btkd->bskgd", p, v, fp8, w_axes=(1,)).reshape(B, S, H, hd)
    x = x + _mm("bshk,hkd->bsd", o, lw["wo"], fp8, a_axes=(2, 3),
                w_axes=(0, 1))
    h = _rms(x, 1.0 + lw["ln2_delta"], eps)
    g = _mm("bsd,df->bsf", h, lw["wg"], fp8)
    u = _mm("bsd,df->bsf", h, lw["wu"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, lw["wd"], fp8)


@partial(jax.jit, static_argnames=("eps", "fp8"))
def _unembed(x, g, head, *, eps, fp8):
    return _mm("bsd,dv->bsv", _rms(x, g, eps), head, fp8)


LAYER_KEYS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd", "ln1_delta",
              "ln2_delta")


def logits(w: dict, c: dict, tokens, first: int, *, fp8: bool = False):
    """Float32 logits (B, S - first, V) at positions first..S-1 of
    `tokens` (B, S)."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = w["embed"][jnp.asarray(tokens)].astype(F32)
    if fp8:
        x = _q8(x, (-1,))
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, {k: w[k][i] for k in LAYER_KEYS}, eps=eps, theta=theta,
                   fp8=fp8)
    return _unembed(x[:, first:], 1.0 + w["final_norm_delta"], w["lm_head"],
                    eps=eps, fp8=fp8)


def gaps(ref, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position: ref (B, N, V), tokens (B, N) -> (B, N)."""
    ref = np.asarray(ref, np.float32)
    tok = np.asarray(tokens)
    picked = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return ref.max(-1) - picked
