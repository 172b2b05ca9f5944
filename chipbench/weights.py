"""Random weights for a served model, made on the device from the seed in
one jitted call, in the type they are served in.

The parameter tree's structure (names, shapes, init kind) is the one the
model declares; the values are drawn here: leaf ``i`` of the flattened
tree from ``fold_in(key, i)``, normal with standard deviation ``scale``
(default ``1/sqrt(fan_in)``, fan_in being the second-to-last dimension),
or ones, or zeros.  The same key gives the same arrays, so the reference
draws the program's weights again after the program has been freed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _leaves(decls):
    from repro.models.params import is_decl

    return jax.tree_util.tree_flatten(decls, is_leaf=is_decl)


def make(decl_trees, keys, dtype=jnp.bfloat16) -> list:
    """Materialize each tree of the model's parameter declarations from
    its key, all in one jitted program."""
    flat = [_leaves(t) for t in decl_trees]
    specs = tuple(tuple((d.shape, d.init, d.scale, d.dtype) for d in leaves)
                  for leaves, _ in flat)

    def build(ks):
        trees = []
        for k, tree in zip(ks, specs):
            out = []
            for i, (shape, init, scale, dt) in enumerate(tree):
                dt = jnp.dtype(dt) if dt else dtype
                if init == "zeros":
                    out.append(jnp.zeros(shape, dt))
                elif init == "ones":
                    out.append(jnp.ones(shape, dt))
                else:
                    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                    std = scale if scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
                    draw = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
                    out.append((draw * std).astype(dt))
            trees.append(out)
        return trees

    made = jax.jit(build)(list(keys))
    return [jax.tree_util.tree_unflatten(treedef, leaves)
            for (_, treedef), leaves in zip(flat, made)]


def agent_key(seed_word: int, agent_index: int):
    return jax.random.fold_in(jax.random.key(seed_word), agent_index)
