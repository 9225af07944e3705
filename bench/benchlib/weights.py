"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference takes
nothing the program made. Only the tree's layout (names and shapes) comes
from the program, through `jax.eval_shape` of its own initialiser, which
computes nothing. Scales follow the usual GPT-2 convention: embeddings
N(0, 0.02²), projections N(0, 1/fan_in), residual-branch outputs (`wo`,
`w_down`) further divided by √(2·layers); norm scales 1, biases 0. Each
matrix's largest magnitude is then moved up to 7·2^e (`_pin_scale`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_RESIDUAL_OUT = {"wo", "w_down"}
_BIASES = {"b", "bias", "bq", "bk", "bv", "bo"}


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed (also past 2**32) as two uint32 words."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be non-negative")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def key_from_words(words):
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, words[0])
    return jax.random.fold_in(key, words[1])


def _pin_scale(w):
    """Move each matrix's largest magnitude up to 7·2^e, the next such
    value: its 4-bit weight scale max|w| / 7 is then a power of two, so
    w / s_w is exact however a compiler implements the division (a tied
    head is quantized inside each compiled step). One element per matrix
    moves, by less than a factor of two."""
    amax = jnp.max(jnp.abs(w), axis=(-2, -1), keepdims=True)
    target = 7.0 * jnp.exp2(jnp.ceil(jnp.log2(amax / 7.0)))
    return jnp.where(jnp.abs(w) == amax, jnp.sign(w) * target, w)


def maker(abstract, n_layers: int):
    """A jitted function words -> weights with the layout of `abstract`."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(words):
        key = key_from_words(words)
        out = []
        for i, (path, leaf) in enumerate(flat):
            name = _leaf_name(path)
            k = jax.random.fold_in(key, i)
            if name == "scale":
                v = jnp.ones(leaf.shape, leaf.dtype)
            elif name in _BIASES:
                v = jnp.zeros(leaf.shape, leaf.dtype)
            else:
                if name == "embed":
                    std = 0.02
                else:
                    std = 1.0 / math.sqrt(leaf.shape[-2])
                    if name in _RESIDUAL_OUT:
                        std /= math.sqrt(2 * n_layers)
                v = _pin_scale(jax.random.normal(k, leaf.shape, jnp.float32)
                               * std).astype(leaf.dtype)
            out.append(v)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)
