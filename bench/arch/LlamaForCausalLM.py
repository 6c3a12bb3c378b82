"""The Llama-style dense decoder, as DeepSeek LLM and Yi publish it.

An architecture module of the benchmark.  A configuration file names its
module by its published `architectures[0]`: `bench/arch/<name>.py`
(`bench.spec.arch`).  Every module has:

  make(c, seed) -> dict
      the reference's weights for configuration file `c`, drawn on the
      device from the seed in one jitted call (`bench.weights.make`)
  program_init_fn(c, seed, expected) -> init_fn
      the same arrays in the served program's parameter tree, checked
      against `expected`, the program's own `jax.eval_shape` of its init
      (`bench.weights.program_init_fn`)
  logits(w, c, tokens, first, *, fp8=False) -> (B, S - first, V) float32
      the plain reference at positions first..S-1 of `tokens` (B, S):
      float32 at `Precision.HIGHEST`, one layer at a time; `fp8=True` is
      its control (`bench.reference`)
  check_program(cfg, c)
      raises ValueError where the program's `ModelConfig` is not the
      file's: every size, and the family and layer kinds this module draws
  launch_args(c) -> list[str]
      the `repro.launch.serve` flags that cut the program as the file's
      `reduced` says
  ffn_flops(c) -> list[int]
      model FLOPs of one token through each layer's feed-forward, the work
      the architecture does there (`bench.flops.decode_step_flops`)

Here: RMSNorm, rotary embedding on halves of each head, causal
grouped-query attention with a 1/sqrt(head_dim) scale, a SwiGLU MLP, a
final RMSNorm and an untied output head; cut by depth alone.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import F32, _mm, _q8, _rms, _rope
from bench.weights import NORM_STD, _normal


def dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    """(L, D, H, K, hd, F, V) of a configuration file."""
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


# ------------------------------------------------------------ the weights
def _draw(c: dict, words) -> dict:
    L, D, H, K, hd, F, V = dims(c)
    std = c["initializer_range"]
    dt = jnp.dtype(c["torch_dtype"])
    shapes = {"embed": (V, D), "wq": (L, D, H, hd), "wk": (L, D, K, hd),
              "wv": (L, D, K, hd), "wo": (L, H, hd, D), "wg": (L, D, F),
              "wu": (L, D, F), "wd": (L, F, D), "lm_head": (D, V)}
    w = {n: _normal(words, i, shape=s, std=std, dtype=dt)
         for i, (n, s) in enumerate(shapes.items())}
    # norm weights as offsets from 1 (g = 1 + delta), in float32
    for j, (n, s) in enumerate({"ln1": (L, D), "ln2": (L, D),
                                "final_norm": (D,)}.items()):
        w[n + "_delta"] = _normal(words, 100 + j, shape=s, std=NORM_STD,
                                  dtype=jnp.float32)
    return w


def program_tree(w: dict) -> dict:
    """The served program's parameter tree over the arrays of `make`: one
    scan segment of identical attention blocks, norms stored as g - 1."""
    block = {"ln1": w["ln1_delta"], "ln2": w["ln2_delta"],
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
             "mlp": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}
    return {"embed": w["embed"], "final_norm": w["final_norm_delta"],
            "segments": [(block,)], "lm_head": w["lm_head"]}


def _draw_program(c: dict, words) -> dict:
    return program_tree(_draw(c, words))


def make(c: dict, seed: int) -> dict:
    return weights.make(_draw, c, seed)


def program_init_fn(c: dict, seed: int, expected):
    return weights.program_init_fn(_draw_program, c, seed, expected)


# ------------------------------------------------------------ the reference
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
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = w["embed"][jnp.asarray(tokens)].astype(F32)
    if fp8:
        x = _q8(x, (-1,))
    for i in range(c["num_hidden_layers"]):
        x = _layer(x, {k: w[k][i] for k in LAYER_KEYS}, eps=eps, theta=theta,
                   fp8=fp8)
    return _unembed(x[:, first:], 1.0 + w["final_norm_delta"], w["lm_head"],
                    eps=eps, fp8=fp8)


# ------------------------------------------------------------ the program
def check_program(cfg, c: dict):
    pairs = {"num_hidden_layers": cfg.num_layers, "hidden_size": cfg.d_model,
             "num_attention_heads": cfg.num_heads,
             "num_key_value_heads": cfg.num_kv_heads,
             "head_dim": cfg.resolved_head_dim,
             "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
             "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
             "torch_dtype": cfg.dtype}
    wrong = {k: (c[k], v) for k, v in pairs.items() if c[k] != v}
    # what this module draws and its reference computes, and nothing else
    kinds = {"family": (cfg.family, "dense"),
             "layer kinds": (set(cfg.pattern), {"attn"}),
             "qkv_bias": (cfg.qkv_bias, False),
             "tie_embeddings": (cfg.tie_embeddings, False),
             "num_experts": (cfg.num_experts, 0),
             "mrope_sections": (cfg.mrope_sections, ())}
    wrong.update({k: (want, got) for k, (got, want) in kinds.items()
                  if got != want})
    if wrong:
        raise ValueError("program config differs from the file "
                         f"(file, program): {wrong}")


def launch_args(c: dict) -> list[str]:
    return ["--num-layers", str(c["num_hidden_layers"])]


def ffn_flops(c: dict) -> list[int]:
    """The SwiGLU MLP: gate, up and down projections."""
    L, D, _, _, _, F, _ = dims(c)
    return [3 * 2 * D * F] * L
