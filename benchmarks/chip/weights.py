"""Seeded random DiT weights, made on the device in one jitted program.

The benchmark makes the weights itself, in the layout the program's
backbone takes (stacked ``[L, ...]`` layer leaves), so that the plain
reference (``reference.py``) reads the same weights without taking any
from the program. Every matrix is drawn N(0, 1/fan_in) with its true
fan-in (for ``wq`` that is d_model, for ``wo`` heads x head_dim), so the
attention logits are of order one as in a trained network; the norm
gains and adaLN weights get the smaller spreads that ``weight_std`` in
the configuration file states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def shapes(model: dict) -> dict:
    """Leaf shapes of the backbone's parameter tree."""
    L, d = model["n_layers"], model["d_model"]
    H, hd, F = model["n_heads"], model["head_dim"], model["d_ff"]
    dz, temb, V = model["latent_dim"], model["time_embed_dim"], \
        model["vocab_size"]
    return {
        "embed": (V, d),
        "ln_f": (d,),
        "lm_head": (d, V),
        "blocks": {
            "ln1": (L, d),
            "ln2": (L, d),
            "attn": {"wq": (L, d, H, hd), "wk": (L, d, H, hd),
                     "wv": (L, d, H, hd), "wo": (L, H, hd, d)},
            "mlp": {"wi": (L, d, F), "wo": (L, F, d)},
            "adaln": (L, d, 6 * d),
        },
        "denoiser": {"in_proj": (dz, d), "out_proj": (d, dz),
                     "t_mlp1": (temb, d), "t_mlp2": (d, d)},
    }


def _fan_in(path: tuple, shape: tuple) -> int:
    name = path[-1]
    if name in ("wq", "wk", "wv"):
        return shape[-3]          # [L, d, H, hd]: contracted over d
    if name == "wo" and path[-2] == "attn":
        return shape[-3] * shape[-2]  # [L, H, hd, d]: over H x hd
    return shape[-2]


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative whole number, also one past
    32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def make(model: dict, weight_std: dict, seed: int):
    """The f32 parameter tree, built on the default device in one call."""
    tree = shapes(model)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(v, int) for v in s))
    specs = []
    for path, shape in leaves:
        names = tuple(getattr(p, "key", p) for p in path)
        std = weight_std.get(names[-1] if names[-1] in weight_std
                             else "/".join(names))
        if std is None:
            std = 1.0 / math.sqrt(_fan_in(names, shape))
        specs.append((shape, float(std)))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(specs))
        return jax.tree_util.tree_unflatten(treedef, [
            std * jax.random.normal(k, shape, jnp.float32)
            for (shape, std), k in zip(specs, keys)])

    return build(seed_key(seed))
