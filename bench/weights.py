"""Random model weights made on the device from the run's seed.

One jitted call fills every leaf of a parameter tree (given as
``ShapeDtypeStruct``s, with their shardings) with normal values, leaf
``i`` from ``fold_in(key, i)``.  A projection matrix gets the standard
deviation ``1/sqrt(fan_in)``, so that every layer's attention and MLP
outputs are as large as the residual stream they join and the logits
depend on the whole trunk, not only on the current token's embedding;
the embedding table, biases and norm scales get :data:`STD`.  The
program under test and the reference get the same values by calling
:func:`make` with the same tree and seed.
"""

from __future__ import annotations

import math

import jax

STD = 0.02

#: leaf name -> how many leading axes (after the stacked-layer axis) are
#: the projection's inputs
FAN_IN_AXES = {"wq": 1, "wk": 1, "wv": 1, "wi": 1, "wg": 1}


def std_for(path, shape) -> float:
    """Standard deviation of one leaf, from its path in the tree."""
    keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
    name, parent = keys[-1], keys[-2] if len(keys) > 1 else None
    if name in FAN_IN_AXES:
        return 1.0 / math.sqrt(shape[1])
    if name == "wo":   # attention [L, heads, head_dim, d]; MLP [L, f, d]
        fan_in = shape[1] * shape[2] if parent == "attn" else shape[1]
        return 1.0 / math.sqrt(fan_in)
    return STD


def make(tree, key_seed: int):
    """The tree's leaves, seeded from ``key_seed``, in one jitted call."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    leaves = [l for _, l in paths]
    stds = [std_for(p, l.shape) for p, l in paths]
    shardings = [getattr(l, "sharding", None) for l in leaves]

    def gen(key):
        return [jax.random.normal(jax.random.fold_in(key, i), l.shape,
                                  l.dtype) * s
                for i, (l, s) in enumerate(zip(leaves, stds))]

    out = jax.jit(gen, out_shardings=shardings if all(shardings) else None)(
        jax.random.PRNGKey(key_seed))
    return jax.tree.unflatten(treedef, out)
