"""Seeded weights for a parameter tree, made on the device in one call.

The models' own ``init`` functions give the tree's structure and leaf
shapes (through ``jax.eval_shape``: no values); the values are drawn
here, so the plain reference and the program read the same weights and
neither made them.  Rule, by a leaf's name: matrices ``w``/``wx``/``wh``
N(0, 1/fan_in), embedding rows ``table``/``model_emb`` N(0, embed_std^2),
biases 0, PReLU slopes ``alpha`` 0.25.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_MATRICES = ("w", "wx", "wh")
_EMBEDDINGS = ("table", "model_emb")


def _leaf_name(path) -> str:
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            return str(k.key)
    raise ValueError(f"parameter leaf without a name: {path}")


def key_of(seed: int):
    """A PRNG key for any non-negative seed (more than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def make(shapes, seed: int, *, embed_std: float):
    """Values for the tree of ``ShapeDtypeStruct`` leaves ``shapes``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        out = []
        for i, (path, sd) in enumerate(flat):
            name = _leaf_name(path)
            k = jax.random.fold_in(key, i)
            if name in _MATRICES:
                v = jax.random.normal(k, sd.shape, jnp.float32) \
                    / jnp.sqrt(jnp.float32(sd.shape[0]))
            elif name in _EMBEDDINGS:
                v = embed_std * jax.random.normal(k, sd.shape, jnp.float32)
            elif name == "b":
                v = jnp.zeros(sd.shape, jnp.float32)
            elif name == "alpha":
                v = jnp.full(sd.shape, 0.25, jnp.float32)
            else:
                raise ValueError(f"no weight rule for leaf {name!r}")
            out.append(v.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(key_of(seed))
