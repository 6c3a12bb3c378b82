"""Pieces every plain float32 reference shares, and its control.

Each architecture's forward is in its own module (`bench/arch/`), written
with these: matmuls in float32 at `Precision.HIGHEST`, RMSNorm, rotary
embedding on halves of each head, and the gap of a token below the best.
A reference shares no code with the program: it reads only the
configuration file and the arrays its architecture's `make` draws.

`fp8=True` is the control: every matmul's two operands rounded to float8
e4m3 (per row of the activations, per output column of the weights), the
precision step below the served bfloat16.
"""
from __future__ import annotations

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


def gaps(ref, tokens) -> np.ndarray:
    """How far each token's reference logit lies below the reference's
    best at its position: ref (B, N, V), tokens (B, N) -> (B, N)."""
    ref = np.asarray(ref, np.float32)
    tok = np.asarray(tokens)
    picked = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return ref.max(-1) - picked
