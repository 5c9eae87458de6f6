"""A plain float32 reference of SD3's MMDiT forward, for the tests.

Written from diffusers' ``SD3Transformer2DModel``, ``JointTransformerBlock``
and ``PatchEmbed`` (Esser et al., arXiv:2403.03206) in straightforward
``jax.numpy``, one sequence at a time, every contraction at
``Precision.HIGHEST``; it imports nothing of ``repro.models``. It reads the
program's parameter tree layout (``x_embed``, ``ctx_embed``, ``t_embed``,
``pooled_embed``, stacked ``blocks``, the pre-only ``last`` block and
``final``) and returns the flow velocity.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def linear(x, p):
    return mm(x, p["w"]) + p["b"]


def layer_norm(x, eps=1e-6):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def sincos_1d(dim, pos):
    """diffusers' ``get_1d_sincos_pos_embed_from_grid``: [sin, cos]."""
    omega = 1.0 / 10000 ** (np.arange(dim // 2, dtype=np.float64)
                            / (dim / 2.0))
    out = np.einsum("m,d->md", pos.reshape(-1), omega)
    return np.concatenate([np.sin(out), np.cos(out)], axis=1)


def pos_embed(dim, max_size, base_size, side):
    """diffusers' ``get_2d_sincos_pos_embed`` on the max grid, cropped to
    the centre ``side`` x ``side`` as ``PatchEmbed.cropped_pos_embed``."""
    g = np.arange(max_size, dtype=np.float64) / (max_size / base_size)
    grid = np.stack(np.meshgrid(g, g), axis=0)      # (w, h) coordinates
    emb = np.concatenate([sincos_1d(dim // 2, grid[0]),
                          sincos_1d(dim // 2, grid[1])], axis=1)
    emb = emb.reshape(max_size, max_size, dim)
    top = (max_size - side) // 2
    return emb[top:top + side, top:top + side].reshape(side * side, dim)


def timestep_sincos(t, dim=256):
    """``Timesteps(dim, flip_sin_to_cos=True, downscale_freq_shift=0)``
    at ``1000 t``: [cos, sin]."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = 1000.0 * jnp.asarray(t, jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)])


def attention(q, k, v, heads):
    """One sequence: q [S, d], k, v [T, d] -> [S, d], non-causal."""
    hd = q.shape[-1] // heads
    split = lambda a: a.reshape(a.shape[0], heads, hd).transpose(1, 0, 2)
    s = jnp.einsum("hqd,hkd->hqk", split(q), split(k),
                   precision=HIGHEST) / math.sqrt(hd)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), split(v),
                   precision=HIGHEST)
    return o.transpose(1, 0, 2).reshape(q.shape[0], -1)


def forward(params, z, t, seq, pooled, *, heads, max_size, base_size):
    """One sequence: z [N, dz], seq [T, dc], pooled [dp] -> [N, dz]."""
    d = params["x_embed"]["w"].shape[1]

    def mlp2(p, h):
        return mm(jax.nn.silu(mm(h, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]

    c = mlp2(params["t_embed"], timestep_sincos(t)) \
        + mlp2(params["pooled_embed"], pooled)
    side = int(round(math.sqrt(z.shape[0])))
    x = linear(z, params["x_embed"]) + jnp.asarray(
        pos_embed(d, max_size, base_size, side), jnp.float32)
    ctx = linear(seq, params["ctx_embed"])
    n = x.shape[0]

    def ada(p, k):
        return jnp.split(linear(jax.nn.silu(c), p), k)

    def ff(p, h):
        return mm(gelu_tanh(mm(h, p["wi"]) + p["bi"]), p["wo"]) + p["bo"]

    def after(s, p, o, g1, sh2, sc2, g2):
        s = s + g1 * linear(o, p["o"])
        return s + g2 * ff(p["mlp"], layer_norm(s) * (1 + sc2) + sh2)

    L = params["blocks"]["x"]["adaln"]["w"].shape[0]
    for i in range(L):
        px = jax.tree.map(lambda a: a[i], params["blocks"]["x"])
        pc = jax.tree.map(lambda a: a[i], params["blocks"]["ctx"])
        sh1, sc1, g1, sh2, sc2, g2 = ada(px["adaln"], 6)
        hx = layer_norm(x) * (1 + sc1) + sh1
        csh1, csc1, cg1, csh2, csc2, cg2 = ada(pc["adaln"], 6)
        hc = layer_norm(ctx) * (1 + csc1) + csh1
        q = jnp.concatenate([linear(hx, px["q"]), linear(hc, pc["q"])])
        k = jnp.concatenate([linear(hx, px["k"]), linear(hc, pc["k"])])
        v = jnp.concatenate([linear(hx, px["v"]), linear(hc, pc["v"])])
        o = attention(q, k, v, heads)
        x = after(x, px, o[:n], g1, sh2, sc2, g2)
        ctx = after(ctx, pc, o[n:], cg1, csh2, csc2, cg2)
    px, pc = params["last"]["x"], params["last"]["ctx"]
    sh1, sc1, g1, sh2, sc2, g2 = ada(px["adaln"], 6)
    hx = layer_norm(x) * (1 + sc1) + sh1
    csc, csh = ada(pc["adaln"], 2)            # AdaLayerNormContinuous
    hc = layer_norm(ctx) * (1 + csc) + csh
    o = attention(linear(hx, px["q"]),
                  jnp.concatenate([linear(hx, px["k"]), linear(hc, pc["k"])]),
                  jnp.concatenate([linear(hx, px["v"]), linear(hc, pc["v"])]),
                  heads)
    x = after(x, px, o, g1, sh2, sc2, g2)
    sc, sh = ada(params["final"]["adaln"], 2)
    return linear(layer_norm(x) * (1 + sc) + sh, params["final"]["out"])


def velocity(params, z, t, cond, *, heads, max_size, base_size):
    """Batched: z [B, N, dz], cond {"seq": [B, T, dc], "pooled": [B, dp]}."""
    return jax.vmap(lambda zz, s, p: forward(
        params, zz, t, s, p, heads=heads, max_size=max_size,
        base_size=base_size))(z, cond["seq"], cond["pooled"])
