"""The DiT family: DiT-XL/2 (Peebles and Xie, arXiv:2212.09748) as the
program's ``dit-xl-2`` backbone serves it, a single-stream transformer
over the latent's tokens, conditioned by one vector added to the latent.

A family module is what the harness knows of one denoiser family; a
configuration file names it (``"family": "dit"``) and ``modules.load``
finds it here. Every family gives:

- ``shapes(model)`` and ``fan_in(path, shape)``: the weight tree's leaf
  shapes and each leaf's fan-in, which ``weights.make`` draws from;
- ``program(conf)``: the program under test, ``(network, schedule,
  null_cond)``, where ``network(params, x, t, cond)`` is the prediction
  network a ``Denoiser`` wraps. Only this function imports the program;
- ``conds(traffic, model, gen, n)``: the conditioning of ``n`` requests,
  drawn from the traffic file's parameters and the run's generator, and
  ``cond_proto(model)``: one request's conditioning as
  ``jax.ShapeDtypeStruct`` leaves (the warm-up's zeros are its shape);
- ``reference_pair(params, x, t, cond, model, quant)``: the plain
  reference's data prediction of the conditional and the unconditional
  branch, which ``reference.py`` combines under guidance;
- ``forward_flops(model, tokens)`` and ``attention_flops(model,
  tokens)``: the FLOPs of one forward over a latent of ``tokens`` tokens,
  and of its S^2 attention.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import HIGHEST, dense, rounding


# ------------------------------------------------------------ the weights
def shapes(model: dict) -> dict:
    """Leaf shapes of the backbone's parameter tree (stacked ``[L, ...]``
    layer leaves, the layout the program's backbone takes)."""
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


def fan_in(path: tuple, shape: tuple) -> int:
    name = path[-1]
    if name in ("wq", "wk", "wv"):
        return shape[-3]          # [L, d, H, hd]: contracted over d
    if name == "wo" and path[-2] == "attn":
        return shape[-3] * shape[-2]  # [L, H, hd, d]: over H x hd
    return shape[-2]


# ------------------------------------------------------------ the program
def program_config(conf: dict):
    """The program's LMConfig at the configuration file's sizes."""
    from repro.configs import get_config
    m = conf["model"]
    return dataclasses.replace(
        get_config(conf["program"]["arch"]),
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab_size=m["vocab_size"], denoiser_latent=m["latent_dim"])


def program(conf: dict):
    """The program's DiT as an eps network whose ``cond`` is added to the
    latent; the unconditional branch takes zeros (``null_cond`` None)."""
    from repro.core import get_schedule
    from repro.launch.sample import as_prediction_network
    from repro.models import build_model
    model = build_model(program_config(conf))
    want = shapes(conf["model"])
    have = jax.tree.map(lambda d: tuple(d.shape), model.param_defs(),
                        is_leaf=lambda d: hasattr(d, "init"))
    if want != have:
        raise SystemExit(f"the program's parameter tree {have} is not the "
                         f"benchmark's {want}")
    schedule = get_schedule(conf["program"]["schedule"])
    network = as_prediction_network(model, schedule,
                                    conf["program"]["prediction"])
    return network, schedule, None


# -------------------------------------------------------- the conditioning
def conds(traffic: dict, model: dict, gen: np.random.Generator,
          n: int) -> list:
    """``n`` requests' vectors, N(0, ``cond_std``^2) in every component."""
    return list(gen.normal(0.0, traffic["cond_std"],
                           (n, model["latent_dim"])).astype(np.float32))


def cond_proto(model: dict):
    return jax.ShapeDtypeStruct((model["latent_dim"],), np.float32)


# ---------------------------------------------------------- the reference
def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def forward(params, z, t, model: dict, quant=None):
    """x0-prediction of the DiT backbone: z [B, S, dz], t scalar.
    ``quant="fp8"`` rounds, where the program keeps bfloat16, to float8:
    the input latent, the residual stream, the branch outputs and both
    operands of every dense projection."""
    r = rounding(quant)
    eps = model["norm_eps"]
    hd = model["head_dim"]
    dp = params["denoiser"]
    half = model["time_embed_dim"] // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.asarray(t, jnp.float32) * freqs
    temb = jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])
    tc = jnp.dot(jax.nn.silu(jnp.dot(temb, dp["t_mlp1"], precision=HIGHEST)),
                 dp["t_mlp2"], precision=HIGHEST)
    x = r(dense(z, dp["in_proj"], r))

    def layer(x, p):
        mod = jnp.dot(tc, p["adaln"], precision=HIGHEST)
        s1, g1, b1, s2, g2, b2 = jnp.split(mod, 6)
        h = _rms(x, p["ln1"], eps) * (1.0 + s1) + b1
        q = dense(h, p["attn"]["wq"], r)
        k = dense(h, p["attn"]["wk"], r)
        v = dense(h, p["attn"]["wv"], r)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / math.sqrt(hd)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=HIGHEST)
        x = r(x + g1 * r(dense(o, p["attn"]["wo"], r, contract=2)))
        h = _rms(x, p["ln2"], eps) * (1.0 + s2) + b2
        m = dense(_gelu_tanh(dense(h, p["mlp"]["wi"], r)),
                  p["mlp"]["wo"], r)
        return r(x + g2 * r(m)), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return dense(_rms(x, params["ln_f"], eps), dp["out_proj"], r)


def reference_pair(params, x, t, cond, model: dict, quant=None):
    """x0 of the conditional branch (``cond`` [B, dz] added to every token
    of x [B, S, dz]) and of the unconditional one (x alone), in one
    forward over both."""
    f = forward(params, jnp.concatenate([x + cond[:, None, :], x]), t,
                model, quant)
    return jnp.split(f, 2)


# ------------------------------------------------------------- the counts
def attention_flops(model: dict, tokens: int) -> int:
    """The S^2 attention of one forward: the scores and the weighted sum
    of values, ``2 L 2 S^2 H hd``."""
    return 2 * model["n_layers"] * 2 * tokens * tokens * model["n_heads"] \
        * model["head_dim"]


def forward_flops(model: dict, tokens: int) -> int:
    """FLOPs (2 per multiply-add) of one backbone forward over one latent
    of ``tokens`` tokens: the dense projections (q, k, v, o and the MLP),
    the S^2 attention, the adaLN modulation and the input, output and
    time-embedding projections. Norms, softmax and elementwise work are
    not counted."""
    d, L = model["d_model"], model["n_layers"]
    H, hd, F = model["n_heads"], model["head_dim"], model["d_ff"]
    dz, temb = model["latent_dim"], model["time_embed_dim"]
    dense_ = 2 * tokens * L * (4 * d * H * hd + 2 * d * F)
    adaln = 2 * L * d * 6 * d
    io = 2 * tokens * dz * d * 2 + 2 * (temb * d + d * d)
    return dense_ + attention_flops(model, tokens) + adaln + io
