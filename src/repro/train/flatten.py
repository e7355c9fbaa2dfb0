"""Deterministic tree <-> flat-vector codec.

The SAFE chain aggregates a single flat f32 vector (the paper's "feature
vector" is our gradient); these helpers define the canonical layout.
Unlike jax.flatten_util.ravel_pytree they also work on abstract
(ShapeDtypeStruct) templates, which the train-step builder uses to size
the ZeRO-1 shards before any real array exists.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def tree_size(tree: Any) -> int:
    return int(sum(np.prod(np.shape(l)) for l in jax.tree.leaves(tree)))


def tree_to_flat(tree: Any) -> jax.Array:
    """Concatenate all leaves (tree order) as f32[P]."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((0,), jnp.float32)
    return jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves])


def flat_to_tree(flat: jax.Array, template: Any) -> Any:
    """Inverse of tree_to_flat; casts each leaf to the template's dtype."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for l in leaves:
        n = int(np.prod(np.shape(l)))
        out.append(flat[off:off + n].reshape(np.shape(l)).astype(l.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)

