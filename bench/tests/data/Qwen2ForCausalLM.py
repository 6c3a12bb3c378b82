"""A dense decoder with attention bias (Qwen2, as CodeQwen1.5 publishes it),
for tests: the architecture a tiny checkout gains with new files only.

The Llama-style decoder of `bench/arch/LlamaForCausalLM.py` with a bias on
the q, k and v projections (`bq`, `bk`, `bv`), added before the rotary
embedding.  It takes every piece but its own layer equations from that
module.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

from bench import spec, weights
from bench.reference import F32, _mm, _q8, _rms, _rope
from bench.weights import _normal

LLAMA = spec.arch({"architectures": ["LlamaForCausalLM"]},
                  Path(__file__).resolve().parents[2])
dims, launch_args, ffn_flops = LLAMA.dims, LLAMA.launch_args, LLAMA.ffn_flops
# as large as the norm weights' offsets, so that the comparison covers them
BIAS_STD = weights.NORM_STD
LAYER_KEYS = LLAMA.LAYER_KEYS + ("bq", "bk", "bv")


def _draw(c: dict, words) -> dict:
    L, _, H, K, hd, _, _ = dims(c)
    w = LLAMA._draw(c, words)
    dt = jnp.dtype(c["torch_dtype"])
    for j, (n, s) in enumerate({"bq": (L, H, hd), "bk": (L, K, hd),
                                "bv": (L, K, hd)}.items()):
        w[n] = _normal(words, 200 + j, shape=s, std=BIAS_STD, dtype=dt)
    return w


def _draw_program(c: dict, words) -> dict:
    w = _draw(c, words)
    tree = LLAMA.program_tree(w)
    tree["segments"][0][0]["attn"].update(bq=w["bq"], bk=w["bk"], bv=w["bv"])
    return tree


def make(c: dict, seed: int) -> dict:
    return weights.make(_draw, c, seed)


def program_init_fn(c: dict, seed: int, expected):
    return weights.program_init_fn(_draw_program, c, seed, expected)


@partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _layer(x, lw, *, eps, theta, fp8):
    B, S, D = x.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = _rms(x, 1.0 + lw["ln1_delta"], eps)
    q = _rope(_mm("bsd,dhk->bshk", h, lw["wq"], fp8) + lw["bq"], pos, theta)
    k = _rope(_mm("bsd,dhk->bshk", h, lw["wk"], fp8) + lw["bk"], pos, theta)
    v = _mm("bsd,dhk->bshk", h, lw["wv"], fp8) + lw["bv"]
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


def logits(w: dict, c: dict, tokens, first: int, *, fp8: bool = False):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = w["embed"][jnp.asarray(tokens)].astype(F32)
    if fp8:
        x = _q8(x, (-1,))
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, {k: w[k][i] for k in LAYER_KEYS}, eps=eps, theta=theta,
                   fp8=fp8)
    return LLAMA._unembed(x[:, first:], 1.0 + w["final_norm_delta"],
                          w["lm_head"], eps=eps, fp8=fp8)


def check_program(cfg, c: dict):
    if not cfg.qkv_bias:
        raise ValueError("the program has no attention bias")
    LLAMA.check_program(dataclasses.replace(cfg, qkv_bias=False), c)
