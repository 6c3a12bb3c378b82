"""Random weights of a dense decoder, drawn on the device from `--seed`.

`make` draws every tensor of the plain reference's layout in one jitted
call; `program_tree` puts the same arrays into the served program's
parameter tree.  The reference never sees the program's tree: it calls
`make` again with the same seed.  Matrices follow the published
initializer (normal, std `initializer_range`); the norm weights are
1 + 0.1 * normal so that the comparison covers them too.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NORM_STD = 0.1


def dims(c: dict) -> tuple[int, int, int, int, int, int, int]:
    """(L, D, H, K, hd, F, V) of a configuration file."""
    return (c["num_hidden_layers"], c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"])


def seed_words(seed: int) -> jnp.ndarray:
    """A seed of up to 64 bits as two uint32 words (jax.random.key keeps
    only the low 32 bits of a larger int)."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def _key(words, i):
    k = jax.random.fold_in(jax.random.key(words[0]), words[1])
    return jax.random.fold_in(k, i)


@partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(words, i, *, shape, std, dtype):
    return (std * jax.random.normal(_key(words, i), shape, jnp.float32)
            ).astype(dtype)


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


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@partial(jax.jit, static_argnames=("frozen",))
def _make(words, *, frozen):
    return _draw(dict(frozen), words)


def make(c: dict, seed: int) -> dict:
    """Every weight of configuration `c` for `seed`, in one jitted call."""
    return _make(seed_words(seed), frozen=_frozen(c))


def program_tree(w: dict) -> dict:
    """The served program's parameter tree over the arrays of `make`: one
    scan segment of identical attention blocks, norms stored as g - 1."""
    block = {"ln1": w["ln1_delta"], "ln2": w["ln2_delta"],
             "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                      "wo": w["wo"]},
             "mlp": {"wg": w["wg"], "wu": w["wu"], "wd": w["wd"]}}
    return {"embed": w["embed"], "final_norm": w["final_norm_delta"],
            "segments": [(block,)], "lm_head": w["lm_head"]}


@partial(jax.jit, static_argnames=("frozen",))
def _make_program(words, *, frozen):
    return program_tree(_draw(dict(frozen), words))


def program_init_fn(c: dict, seed: int, expected):
    """An `init_fn` for `Engine.register`: the program's tree for `seed`,
    made on the device in one jitted call.  `expected` is the program's own
    `jax.eval_shape` of its init; a tree of another layout is an error,
    since the reference would then compare other weights."""
    words = seed_words(seed)
    fn = partial(_make_program, words, frozen=_frozen(c))
    got = jax.eval_shape(fn)
    same = (jax.tree.structure(got) == jax.tree.structure(expected)
            and all(a.shape == b.shape and a.dtype == b.dtype
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(expected))))
    if not same:
        raise ValueError("the program's parameter layout differs from the "
                         f"benchmark's: {expected} vs {got}")
    return fn
