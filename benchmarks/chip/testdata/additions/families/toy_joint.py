"""A toy two-input family: the latent's tokens attend jointly over
themselves and a short context sequence, and a pooled vector and the
time shift every latent token. ``cond`` is a dict, ``{"seq": [T, d_c],
"pooled": [d_p]}``, and the unconditional branch takes a fixed null
prompt that is not zero. The program under test is the server
(``Denoiser``, executor, ``ServeEngine``) around a network written here
on a rectified-flow schedule of its own, all in float32, so that it
agrees with the reference to float32 rounding."""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import dense, rounding


def shapes(model: dict) -> dict:
    d = model["d_model"]
    return {"w_in": (model["latent_dim"], d), "w_ctx": (model["ctx_dim"], d),
            "w_pool": (model["pooled_dim"], d),
            "w_t": (model["time_embed_dim"], d),
            "w_out": (d, model["latent_dim"])}


def fan_in(path: tuple, shape: tuple) -> int:
    return shape[0]


def _null(model: dict) -> dict:
    T, dc, dp = model["ctx_tokens"], model["ctx_dim"], model["pooled_dim"]
    return {"seq": np.linspace(-1.0, 1.0, T * dc, dtype=np.float32)
            .reshape(T, dc),
            "pooled": np.linspace(0.5, -0.5, dp, dtype=np.float32)}


def _forward(params, x, t, c, model: dict, mm):
    """x0 of one latent x [S, dz] under cond c; ``mm`` multiplies."""
    half = model["time_embed_dim"] // 2
    ang = jnp.asarray(t, jnp.float32) * jnp.exp(
        -math.log(10000.0) * jnp.arange(half) / half)
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])
    h = mm(x, params["w_in"]) + mm(c["pooled"], params["w_pool"]) \
        + mm(temb, params["w_t"])
    joint = jnp.concatenate([h, mm(c["seq"], params["w_ctx"])])
    s = mm(h, joint.T) / math.sqrt(model["d_model"])
    h = h + mm(jax.nn.softmax(s, -1), joint)
    return mm(jnp.tanh(h), params["w_out"])


def program(conf: dict):
    from repro.core.schedules import NoiseSchedule

    model, block = conf["model"], conf["schedule"]

    @dataclasses.dataclass(frozen=True)
    class RectifiedFlow(NoiseSchedule):
        t_start: float = block["t_start"]
        t_end: float = block["t_end"]

        def log_alpha(self, t):
            return np.log1p(-np.asarray(t, np.float64))

        def log_sigma(self, t):
            return np.log(np.asarray(t, np.float64))

        def t_of_lam(self, lam):
            return 1.0 / (1.0 + np.exp(np.asarray(lam, np.float64)))

        def log_alpha_j(self, t):
            return jnp.log1p(-t)

        def log_sigma_j(self, t):
            return jnp.log(t)

    def network(params, x, t, cond):
        one = lambda x, c: _forward(params, x, t, c, model, jnp.dot)
        return one(x, cond) if x.ndim == 2 else jax.vmap(one)(x, cond)

    null = jax.tree.map(jnp.asarray, _null(model))
    return network, RectifiedFlow(), null


def conds(traffic: dict, model: dict, gen: np.random.Generator,
          n: int) -> list:
    seq = gen.normal(0.0, traffic["ctx_std"], (
        n, model["ctx_tokens"], model["ctx_dim"])).astype(np.float32)
    pooled = gen.normal(0.0, traffic["pooled_std"], (
        n, model["pooled_dim"])).astype(np.float32)
    return [{"seq": seq[i], "pooled": pooled[i]} for i in range(n)]


def cond_proto(model: dict):
    return {"seq": jax.ShapeDtypeStruct(
                (model["ctx_tokens"], model["ctx_dim"]), np.float32),
            "pooled": jax.ShapeDtypeStruct((model["pooled_dim"],),
                                           np.float32)}


def reference_pair(params, x, t, cond, model: dict, quant=None):
    r = rounding(quant)
    f = jax.vmap(lambda x, c: _forward(
        params, x, t, c, model, lambda a, b: dense(a, b, r)))
    null = jax.tree.map(lambda n, c: jnp.broadcast_to(n, c.shape),
                        _null(model), cond)
    return f(x, cond), f(x, null)


def attention_flops(model: dict, tokens: int) -> int:
    return 2 * 2 * tokens * (tokens + model["ctx_tokens"]) * model["d_model"]


def forward_flops(model: dict, tokens: int) -> int:
    d, dz = model["d_model"], model["latent_dim"]
    return 2 * tokens * 2 * dz * d + 2 * model["ctx_tokens"] \
        * model["ctx_dim"] * d + 2 * (model["pooled_dim"]
                                      + model["time_embed_dim"]) * d \
        + attention_flops(model, tokens)
