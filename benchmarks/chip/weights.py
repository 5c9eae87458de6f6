"""Seeded random weights, made on the device in one jitted program.

The benchmark makes the weights itself, in the layout the program's
backbone takes (the configuration's family module gives the leaf
shapes, ``shapes``), so that the plain reference (``reference.py``)
reads the same weights without taking any from the program. Every
matrix is drawn N(0, 1/fan_in) with its true fan-in (the family's
``fan_in``: for DiT's ``wq`` that is d_model, for its ``wo`` heads x
head_dim), so the attention logits are of order one as in a trained
network; the leaves that ``weight_std`` in the configuration file names,
by leaf name or by path, get the spreads it states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number, also one past
    32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def specs(family, model: dict, weight_std: dict):
    """Each leaf's path, shape and spread in the order ``make`` draws
    them, and the tree's structure."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        family.shapes(model), is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(v, int) for v in s))
    out = []
    for path, shape in leaves:
        names = tuple(getattr(p, "key", p) for p in path)
        std = weight_std.get(names[-1] if names[-1] in weight_std
                             else "/".join(names))
        if std is None:
            std = 1.0 / math.sqrt(family.fan_in(names, shape))
        out.append((names, shape, float(std)))
    return out, treedef


def make(family, model: dict, weight_std: dict, seed: int):
    """The f32 parameter tree, built on the default device in one call."""
    leaves, treedef = specs(family, model, weight_std)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree_util.tree_unflatten(treedef, [
            std * jax.random.normal(k, shape, jnp.float32)
            for (_, shape, std), k in zip(leaves, keys)])

    return build(seed_key(seed))
