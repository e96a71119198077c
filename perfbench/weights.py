"""Seeded random weights, made on the device in one jitted call, in the type
they are served in."""

import math

import jax
import jax.numpy as jnp


def _leaf(key, path, shape, dtype):
    name = jax.tree_util.keystr(path)
    if len(shape) <= 1:
        return jnp.ones(shape, dtype)              # norm weights
    if "embedding" in name:
        std = 1.0
    else:
        # [in, ...out] kernels; stacked experts are [E, in, out]
        fan_in = shape[1] if len(shape) == 3 and "moe" in name else shape[0]
        std = 1.0 / math.sqrt(fan_in)
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


def seeded_weights(shapes, key, dtype=jnp.bfloat16):
    """A tree like ``shapes`` (from ``jax.eval_shape(model.init, ...)``) of
    normal weights with standard deviation 1/sqrt(fan-in), norm weights 1."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = [_leaf(jax.random.fold_in(key, i), path, tuple(s.shape), dtype)
               for i, (path, s) in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(key)
