"""The MMDiT family: Stable Diffusion 3's joint diffusion transformer
(Esser et al., arXiv:2403.03206), as the program's ``sd3-medium``
backbone (``models/mmdit.py``) serves it: image patch tokens and text
tokens in two streams with weights of their own, joint attention over
both, adaLN-Zero from the time and a pooled text vector, predicting
rectified flow's velocity ``u = eps - x0``.

A request's ``cond`` is ``{"seq": [ctx_tokens, ctx_dim], "pooled":
[pooled_dim]}``, drawn N(0, 1) per component (scaled by the traffic
file's ``seq_std`` and ``pooled_std``). The unconditional branch takes
a fixed null prompt that is not zero, as SD3's empty-prompt encoding is
not: a draw of the same law from ``NULL_SEED``, the same in the program
and in the reference.

``families/dit.py`` says what every family gives. This one adds
``context_flops(model)``, the text stream's FLOPs of one forward, which
``metrics/context_stream_roofline.py`` reads.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import HIGHEST, dense, rounding

#: the seed of the null prompt's draw
NULL_SEED = 20240305
#: query rows per block of the reference's attention: 8 sequences of
#: 4250^2 float32 scores would take 14 GB at once
Q_BLOCK = 128


# ------------------------------------------------------------ the weights
def _lin(i: int, o: int, L: int | None = None) -> dict:
    lead = () if L is None else (L,)
    return {"w": lead + (i, o), "b": lead + (o,)}


def _stream(d: int, F: int, L: int | None = None) -> dict:
    lead = () if L is None else (L,)
    return {"adaln": _lin(d, 6 * d, L),
            "q": _lin(d, d, L), "k": _lin(d, d, L), "v": _lin(d, d, L),
            "o": _lin(d, d, L),
            "mlp": {"wi": lead + (d, F), "bi": lead + (F,),
                    "wo": lead + (F, d), "bo": lead + (d,)}}


def shapes(model: dict) -> dict:
    """Leaf shapes of the backbone's parameter tree: the first ``n_layers
    - 1`` joint blocks stacked ``[L - 1, ...]`` per stream, then the last
    block, whose text stream holds only its adaLN, key and value."""
    L, d, F = model["n_layers"], model["d_model"], model["d_ff"]
    mlp2 = lambda i: {"w1": (i, d), "b1": (d,), "w2": (d, d), "b2": (d,)}
    return {
        "x_embed": _lin(model["latent_dim"], d),
        "ctx_embed": _lin(model["ctx_dim"], d),
        "t_embed": mlp2(model["time_embed_dim"]),
        "pooled_embed": mlp2(model["pooled_dim"]),
        "blocks": {"x": _stream(d, F, L - 1), "ctx": _stream(d, F, L - 1)},
        "last": {"x": _stream(d, F),
                 "ctx": {"adaln": _lin(d, 2 * d), "k": _lin(d, d),
                         "v": _lin(d, d)}},
        "final": {"adaln": _lin(d, 2 * d), "out": _lin(d, model["latent_dim"])},
    }


def fan_in(path: tuple, shape: tuple) -> int:
    """Every matrix is ``[..., in, out]``; a bias (named ``b...``) has no
    fan-in of its own: the configuration gives biases their spread."""
    return shape[-2] if not path[-1].startswith("b") else shape[-1]


# ------------------------------------------------------------ the program
def program_config(conf: dict):
    """The program's MMDiTConfig at the configuration file's sizes."""
    from repro.configs import get_config
    m = conf["model"]
    return dataclasses.replace(
        get_config(conf["program"]["arch"]),
        n_layers=m["n_layers"], d_model=m["d_model"], n_heads=m["n_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], patch_size=m["patch_size"],
        in_channels=m["in_channels"], sample_size=m["sample_size"],
        ctx_dim=m["ctx_dim"], pooled_dim=m["pooled_dim"],
        time_embed_dim=m["time_embed_dim"],
        pos_embed_max_size=m["pos_embed_max_size"], norm_eps=m["norm_eps"])


def program(conf: dict):
    """The program's MMDiT as a flow-velocity network that takes ``cond``
    itself; the unconditional branch takes the null prompt."""
    from repro.core import get_schedule
    from repro.launch.sample import as_conditioned_network
    from repro.models import build_model
    model = build_model(program_config(conf))
    want = shapes(conf["model"])
    have = jax.tree.map(lambda d: tuple(d.shape), model.param_defs(),
                        is_leaf=lambda d: hasattr(d, "init"))
    if want != have:
        raise SystemExit(f"the program's parameter tree {have} is not the "
                         f"benchmark's {want}")
    block = conf["schedule"]
    schedule = get_schedule(conf["program"]["schedule"],
                            t_start=block["t_start"], t_end=block["t_end"])
    network = as_conditioned_network(model, schedule,
                                     conf["program"]["prediction"])
    return network, schedule, jax.tree.map(jnp.asarray,
                                           null_prompt(conf["model"]))


# -------------------------------------------------------- the conditioning
@functools.cache
def _null(T: int, dc: int, dp: int):
    gen = np.random.default_rng(NULL_SEED)
    return (gen.standard_normal((T, dc), np.float32),
            gen.standard_normal((dp,), np.float32))


def null_prompt(model: dict) -> dict:
    seq, pooled = _null(model["ctx_tokens"], model["ctx_dim"],
                        model["pooled_dim"])
    return {"seq": seq, "pooled": pooled}


def conds(traffic: dict, model: dict, gen: np.random.Generator,
          n: int) -> list:
    """``n`` requests' text sequences and pooled vectors."""
    seq = gen.standard_normal((n, model["ctx_tokens"], model["ctx_dim"]),
                              np.float32) * np.float32(traffic["seq_std"])
    pooled = gen.standard_normal((n, model["pooled_dim"]), np.float32) \
        * np.float32(traffic["pooled_std"])
    return [{"seq": seq[i], "pooled": pooled[i]} for i in range(n)]


def cond_proto(model: dict):
    return {"seq": jax.ShapeDtypeStruct(
                (model["ctx_tokens"], model["ctx_dim"]), np.float32),
            "pooled": jax.ShapeDtypeStruct((model["pooled_dim"],),
                                           np.float32)}


# ---------------------------------------------------------- the reference
def _ln(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1,
                                             keepdims=True) + eps)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _pos(model: dict, side: int) -> np.ndarray:
    """The 2-D sin-cos table of the ``pos_embed_max_size`` grid, cropped
    to the centre ``side`` x ``side``, in float64: per token [sin, cos]
    of its column, then of its row."""
    m, d = model["pos_embed_max_size"], model["d_model"]
    base = model["sample_size"] // model["patch_size"]
    q = d // 4
    omega = 1.0 / 10000.0 ** (np.arange(q) / q)
    top = (m - side) // 2
    coord = np.arange(top, top + side) / (m / base)
    ang = coord[:, None] * omega
    one = np.concatenate([np.sin(ang), np.cos(ang)], axis=1)   # [side, 2q]
    return np.concatenate([np.repeat(one[None], side, 0),
                           np.repeat(one[:, None], side, 1)],
                          axis=-1).reshape(side * side, d)


def _attend(q, k, v, heads):
    """[B, S, d] queries over [B, T, d] keys, in blocks of Q_BLOCK rows."""
    B, S, d = q.shape
    hd = d // heads
    split = lambda a: a.reshape(B, a.shape[1], heads, hd)
    k, v = split(k), split(v)
    n = -(-S // Q_BLOCK)
    qb = jnp.pad(split(q) / math.sqrt(hd),
                 ((0, 0), (0, n * Q_BLOCK - S), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(B, n, Q_BLOCK, heads, hd), 1, 0)

    def one(qi):
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    o = jnp.moveaxis(jax.lax.map(one, qb), 0, 1)
    return o.reshape(B, n * Q_BLOCK, d)[:, :S]


def forward(params, z, t, seq, pooled, model: dict, quant=None):
    """Flow velocity of the MMDiT: z [B, N, dz], seq [B, T, dc], pooled
    [B, dp], t scalar. ``quant="fp8"`` rounds, where the program keeps
    bfloat16, to float8: the input latent and text, both streams, the
    branch outputs and both operands of every dense projection."""
    r = rounding(quant)
    eps, H = model["norm_eps"], model["n_heads"]
    lin = lambda x, p: dense(x, p["w"], r) + p["b"]

    def mlp2(p, h):
        h = jnp.dot(h, p["w1"], precision=HIGHEST) + p["b1"]
        return jnp.dot(jax.nn.silu(h), p["w2"], precision=HIGHEST) + p["b2"]

    half = model["time_embed_dim"] // 2
    ang = 1000.0 * jnp.asarray(t, jnp.float32) * jnp.exp(
        -math.log(10000.0) * jnp.arange(half) / half)
    c = mlp2(params["t_embed"], jnp.concatenate([jnp.cos(ang),
                                                 jnp.sin(ang)])) \
        + mlp2(params["pooled_embed"], pooled)             # [B, d]
    sc = jax.nn.silu(c)

    def ada(p, k):
        m = jnp.dot(sc, p["w"], precision=HIGHEST) + p["b"]
        return [a[:, None, :] for a in jnp.split(m, k, axis=-1)]

    def modulate(s, shift, scale):
        return _ln(s, eps) * (1.0 + scale) + shift

    def qkv(h, p, names):
        return [r(lin(h, p[n])) for n in names]

    def after(s, p, o, g1, sh2, sc2, g2):
        s = r(s + g1 * r(lin(o, p["o"])))
        m = lin(_gelu_tanh(lin(modulate(s, sh2, sc2), {
            "w": p["mlp"]["wi"], "b": p["mlp"]["bi"]})),
            {"w": p["mlp"]["wo"], "b": p["mlp"]["bo"]})
        return r(s + g2 * r(m))

    N = z.shape[1]
    side = math.isqrt(N)
    x = r(lin(r(z), params["x_embed"])
          + jnp.asarray(_pos(model, side), jnp.float32))
    ctx = r(lin(r(seq), params["ctx_embed"]))

    def block(carry, p):
        x, ctx = carry
        sh1, sc1, g1, sh2, sc2, g2 = ada(p["x"]["adaln"], 6)
        c_sh1, c_sc1, c_g1, c_sh2, c_sc2, c_g2 = ada(p["ctx"]["adaln"], 6)
        qx, kx, vx = qkv(modulate(x, sh1, sc1), p["x"], "qkv")
        qc, kc, vc = qkv(modulate(ctx, c_sh1, c_sc1), p["ctx"], "qkv")
        o = _attend(jnp.concatenate([qx, qc], 1),
                    jnp.concatenate([kx, kc], 1),
                    jnp.concatenate([vx, vc], 1), H)
        return (after(x, p["x"], o[:, :N], g1, sh2, sc2, g2),
                after(ctx, p["ctx"], o[:, N:], c_g1, c_sh2, c_sc2,
                      c_g2)), None

    (x, ctx), _ = jax.lax.scan(block, (x, ctx), params["blocks"])
    px, pc = params["last"]["x"], params["last"]["ctx"]
    sh1, sc1, g1, sh2, sc2, g2 = ada(px["adaln"], 6)
    c_sc, c_sh = ada(pc["adaln"], 2)
    qx, kx, vx = qkv(modulate(x, sh1, sc1), px, "qkv")
    kc, vc = qkv(modulate(ctx, c_sh, c_sc), pc, "kv")
    o = _attend(qx, jnp.concatenate([kx, kc], 1),
                jnp.concatenate([vx, vc], 1), H)
    x = after(x, px, o, g1, sh2, sc2, g2)
    f_sc, f_sh = ada(params["final"]["adaln"], 2)
    return lin(modulate(x, f_sh, f_sc), params["final"]["out"])


def reference_pair(params, x, t, cond, model: dict, quant=None):
    """x0 of the conditional branch and of the unconditional one (the
    null prompt), in one forward over both: the velocity u turned into
    x0 = x - t u, rectified flow's identity."""
    null = null_prompt(model)
    B = x.shape[0]
    seq = jnp.concatenate([cond["seq"], jnp.broadcast_to(
        null["seq"], cond["seq"].shape)])
    pooled = jnp.concatenate([cond["pooled"], jnp.broadcast_to(
        null["pooled"], cond["pooled"].shape)])
    xx = jnp.concatenate([x, x])
    u = forward(params, xx, t, seq, pooled, model, quant)
    x0 = xx - jnp.asarray(t, jnp.float32) * u
    return x0[:B], x0[B:]


# ------------------------------------------------------------- the counts
def attention_flops(model: dict, tokens: int) -> int:
    """The S^2 attention of one forward over ``tokens`` image tokens and
    the text's: scores and the weighted sum of values, ``4 S^2 d`` in
    each full block over the joint ``S = tokens + ctx_tokens``, and ``4
    tokens S d`` in the last, whose text rows ask no queries."""
    S = tokens + model["ctx_tokens"]
    d, L = model["n_heads"] * model["head_dim"], model["n_layers"]
    return 4 * d * S * ((L - 1) * S + tokens)


def context_flops(model: dict) -> int:
    """The text stream's FLOPs of one forward (all under the program's
    ``context_stream`` scope): its embedder, and per block its adaLN
    modulation, its projections and its MLP; the last block only its
    adaLN (2 d), key and value."""
    d, F, L = model["d_model"], model["d_ff"], model["n_layers"]
    T = model["ctx_tokens"]
    full = 2 * d * 6 * d + 2 * T * (4 * d * d + 2 * d * F)
    last = 2 * d * 2 * d + 2 * T * 2 * d * d
    return 2 * T * model["ctx_dim"] * d + (L - 1) * full + last


def forward_flops(model: dict, tokens: int) -> int:
    """FLOPs (2 per multiply-add) of one forward over ``tokens`` image
    tokens: the image stream's adaLN, projections and MLP, the text
    stream's (``context_flops``), the joint attention, the embedders,
    the final modulation and the output projection. Norms, softmax and
    elementwise work are not counted."""
    d, F, L = model["d_model"], model["d_ff"], model["n_layers"]
    dz, temb = model["latent_dim"], model["time_embed_dim"]
    image = L * (2 * d * 6 * d + 2 * tokens * (4 * d * d + 2 * d * F))
    embed = 2 * tokens * dz * d * 2 + 2 * (temb * d + d * d) \
        + 2 * (model["pooled_dim"] * d + d * d) + 2 * d * 2 * d
    return image + context_flops(model) + attention_flops(model, tokens) \
        + embed
