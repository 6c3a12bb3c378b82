"""Random weights drawn on the device from `--seed`: what every
architecture module (`bench/arch/`) shares.

An architecture's `draw(c, words)` lays out every tensor of its plain
reference; `make` runs it in one jitted call.  `program_init_fn` runs the
architecture's `draw` of the served program's parameter tree, which puts
the same arrays into the program's layout.  The reference never sees the
program's tree: it calls `make` again with the same seed.  Matrices follow
the published initializer (normal, std `initializer_range`); norm weights
are 1 + NORM_STD * normal, so that the comparison covers them too.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

NORM_STD = 0.1


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


def _frozen(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@partial(jax.jit, static_argnames=("draw", "frozen"))
def _make(words, *, draw, frozen):
    return draw(dict(frozen), words)


def make(draw, c: dict, seed: int) -> dict:
    """`draw`'s tensors of configuration `c` for `seed`, in one jitted
    call."""
    return _make(seed_words(seed), draw=draw, frozen=_frozen(c))


def program_init_fn(draw, c: dict, seed: int, expected):
    """An `init_fn` for `Engine.register`: `draw`'s program tree for
    `seed`, made on the device in one jitted call.  `expected` is the
    program's own `jax.eval_shape` of its init; a tree of another layout is
    an error, since the reference would then compare other weights."""
    fn = partial(_make, seed_words(seed), draw=draw, frozen=_frozen(c))
    got = jax.eval_shape(fn)
    same = (jax.tree.structure(got) == jax.tree.structure(expected)
            and all(a.shape == b.shape and a.dtype == b.dtype
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(expected))))
    if not same:
        raise ValueError("the program's parameter layout differs from the "
                         f"benchmark's: {expected} vs {got}")
    return fn
