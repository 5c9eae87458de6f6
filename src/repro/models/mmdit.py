"""MMDiT: the joint (two-stream) diffusion transformer of Stable Diffusion
3 (Esser et al., arXiv:2403.03206), as diffusers' ``SD3Transformer2DModel``
and ``JointTransformerBlock`` compute it.

Image patch tokens and text tokens keep weights of their own in every
block, each stream modulated by adaLN-Zero from one conditioning vector
(time and pooled text), and meet in one joint attention over the
concatenated sequence. The last block's text stream is "pre-only": it
gives keys and values, and nothing of it is used after the attention.

    c      = MLP_t(sincos_256(1000 t)) + MLP_p(pooled)
    x      = z @ W_patch + b + pos       ctx = seq @ W_ctx + b
    block, each stream s in (x, ctx):
        (sh1, sc1, g1, sh2, sc2, g2) = Linear(SiLU(c))
        h_s = LN(s)(1 + sc1) + sh1;  q_s, k_s, v_s = h_s @ W_{q,k,v} + b
        o   = attention([q_x; q_ctx], [k_x; k_ctx], [v_x; v_ctx])
        s  += g1 (o_s @ W_o + b);  s += g2 FF(LN(s)(1 + sc2) + sh2)
    out    = (LN(x)(1 + sc) + sh) @ W_out + b,   (sc, sh) = Linear(SiLU(c))

LN is LayerNorm without affine parameters (eps 1e-6), FF a tanh-GELU
MLP, and every linear layer has a bias. ``pos`` is the 2-D sin-cos table
of a ``pos_embed_max_size``-square grid, positions scaled by ``base_size
/ pos_embed_max_size``, cropped to the latent's centre. The network
predicts the flow velocity ``u = eps - x0`` of rectified flow.

Layer leaves are stacked ``[L - 1, ...]`` and scanned; the pre-only last
block follows the scan. The conditioning path stays float32 (as
``TransformerLM._tcond``), the streams compute in ``cfg.dtype``. The
text stream's per-block work runs under ``named_scope("context_stream")``
and the joint attention under ``attention`` (``attention._sdpa``), so a
profile names both.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .attention import _sdpa
from .common import ParamDef, mlp_apply
from .transformer import TransformerLM, timestep_embedding

__all__ = ["MMDiTConfig", "MMDiT"]


@dataclasses.dataclass(frozen=True)
class MMDiTConfig:
    name: str = "mmdit"
    #: joint blocks; the last one's text stream is pre-only
    n_layers: int = 24
    d_model: int = 1536
    n_heads: int = 24
    head_dim: int = 64
    d_ff: int = 6144
    patch_size: int = 2
    in_channels: int = 16
    #: the latent's side in pixels of the latent (128 at 1024 px)
    sample_size: int = 128
    ctx_dim: int = 4096
    pooled_dim: int = 2048
    time_embed_dim: int = 256
    pos_embed_max_size: int = 192
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    #: what the network predicts (rectified flow's velocity)
    prediction: str = "flow"

    @property
    def latent_dim(self) -> int:
        """Values per patch token: patch_size^2 x in_channels."""
        return self.patch_size ** 2 * self.in_channels

    @property
    def base_size(self) -> int:
        return self.sample_size // self.patch_size

    def param_count(self) -> int:
        """Parameters, analytic (the pre-only last block holds no query
        projection: its queries would be discarded)."""
        d, F, L = self.d_model, self.d_ff, self.n_layers
        lin = lambda i, o: i * o + o
        stream = lin(d, 6 * d) + 4 * lin(d, d) + lin(d, F) + lin(F, d)
        last_ctx = lin(d, 2 * d) + 2 * lin(d, d)
        embed = (lin(self.latent_dim, d) + lin(self.ctx_dim, d)
                 + lin(self.time_embed_dim, d) + lin(d, d)
                 + lin(self.pooled_dim, d) + lin(d, d))
        final = lin(d, 2 * d) + lin(d, self.latent_dim)
        return (2 * L - 1) * stream + last_ctx + embed + final


def _linear_defs(i: int, o: int) -> dict:
    return {"w": ParamDef((i, o), ("embed", None), "scaled"),
            "b": ParamDef((o,), (None,), "zeros")}


def _layer_norm(x, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def pos_table(cfg: MMDiTConfig, side: int) -> jnp.ndarray:
    """The 2-D sin-cos position table of the centre ``side`` x ``side``
    patches, ``[side^2, d]`` float32, row-major over (row, column): the
    first half of each row embeds the column, the second the row, each
    as [sin, cos] over d/4 frequencies."""
    m, d = cfg.pos_embed_max_size, cfg.d_model
    if side > m:
        raise ValueError(f"a {side}x{side} patch grid exceeds "
                         f"pos_embed_max_size {m}")
    top = (m - side) // 2
    coord = (top + jnp.arange(side, dtype=jnp.float32)) * (cfg.base_size / m)
    q = d // 4
    omega = 1.0 / 10000.0 ** (jnp.arange(q, dtype=jnp.float32) / q)

    def one(pos):
        ang = pos[:, None] * omega
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)

    emb = one(coord)                      # the same for rows and columns
    return jnp.concatenate([
        jnp.broadcast_to(emb[None, :, :], (side, side, 2 * q)),
        jnp.broadcast_to(emb[:, None, :], (side, side, 2 * q)),
    ], axis=-1).reshape(side * side, d)


class MMDiT:
    def __init__(self, cfg: MMDiTConfig):
        self.cfg = cfg

    # ------------------------------------------------------------ params
    def _stream_defs(self) -> dict:
        d, F = self.cfg.d_model, self.cfg.d_ff
        return {
            "adaln": _linear_defs(d, 6 * d),
            "q": _linear_defs(d, d), "k": _linear_defs(d, d),
            "v": _linear_defs(d, d), "o": _linear_defs(d, d),
            "mlp": {"wi": ParamDef((d, F), ("embed", "mlp"), "scaled"),
                    "bi": ParamDef((F,), ("mlp",), "zeros"),
                    "wo": ParamDef((F, d), ("mlp", "embed"), "scaled"),
                    "bo": ParamDef((d,), (None,), "zeros")},
        }

    def param_defs(self) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        mlp2 = lambda i: {"w1": ParamDef((i, d), (None, "embed"), "scaled"),
                          "b1": ParamDef((d,), (None,), "zeros"),
                          "w2": ParamDef((d, d), ("embed", None), "scaled"),
                          "b2": ParamDef((d,), (None,), "zeros")}
        stream = self._stream_defs()
        return {
            "x_embed": _linear_defs(cfg.latent_dim, d),
            "ctx_embed": _linear_defs(cfg.ctx_dim, d),
            "t_embed": mlp2(cfg.time_embed_dim),
            "pooled_embed": mlp2(cfg.pooled_dim),
            "blocks": {"x": TransformerLM._stack(stream, cfg.n_layers - 1),
                       "ctx": TransformerLM._stack(stream, cfg.n_layers - 1)},
            "last": {"x": stream,
                     "ctx": {"adaln": _linear_defs(d, 2 * d),
                             "k": _linear_defs(d, d),
                             "v": _linear_defs(d, d)}},
            "final": {"adaln": _linear_defs(d, 2 * d),
                      "out": _linear_defs(d, cfg.latent_dim)},
        }

    # ------------------------------------------------------------ pieces
    def _lin(self, x, p):
        dt = self.cfg.dtype
        return x @ p["w"].astype(dt) + p["b"].astype(dt)

    def _heads(self, x):
        return x.reshape(*x.shape[:2], self.cfg.n_heads, self.cfg.head_dim)

    def _mod(self, c, p, n):
        """adaLN's ``n`` [B, 1, d] float32 rows from the signal c."""
        m = jax.nn.silu(c) @ p["w"].astype(jnp.float32) \
            + p["b"].astype(jnp.float32)
        return [r[:, None, :] for r in jnp.split(m, n, axis=-1)]

    def _modulate(self, s, shift, scale):
        ln = _layer_norm(s, self.cfg.norm_eps)
        return (ln * (1.0 + scale) + shift).astype(self.cfg.dtype)

    def _pre(self, s, p, c):
        """A full stream's half-block before the attention: its
        modulation rows and its q, k, v heads."""
        sh1, sc1, g1, sh2, sc2, g2 = self._mod(c, p["adaln"], 6)
        h = self._modulate(s, sh1, sc1)
        qkv = [self._heads(self._lin(h, p[n])) for n in ("q", "k", "v")]
        return qkv, (g1, sh2, sc2, g2)

    def _post(self, s, p, o, mods):
        """A full stream's half-block after the attention."""
        dt = self.cfg.dtype
        g1, sh2, sc2, g2 = mods
        s = s + g1.astype(dt) * self._lin(o.reshape(*o.shape[:2], -1),
                                          p["o"])
        w = {k: v.astype(dt) for k, v in p["mlp"].items()}
        m = mlp_apply(w, self._modulate(s, sh2, sc2), "gelu", False)
        return s + g2.astype(dt) * m

    def _joint(self, x, ctx, p, c):
        """One full joint block over both streams."""
        qkv_x, mx = self._pre(x, p["x"], c)
        with jax.named_scope("context_stream"):
            qkv_c, mc = self._pre(ctx, p["ctx"], c)
        q, k, v = (jnp.concatenate([a, b], axis=1)
                   for a, b in zip(qkv_x, qkv_c))
        o = _sdpa(q, k, v, causal=False)
        n = x.shape[1]
        x = self._post(x, p["x"], o[:, :n], mx)
        with jax.named_scope("context_stream"):
            ctx = self._post(ctx, p["ctx"], o[:, n:], mc)
        return x, ctx

    def _last(self, x, ctx, p, c):
        """The last block: the text stream gives keys and values only."""
        (q, kx, vx), mx = self._pre(x, p["x"], c)
        with jax.named_scope("context_stream"):
            sc, sh = self._mod(c, p["ctx"]["adaln"], 2)
            h = self._modulate(ctx, sh, sc)
            kc, vc = (self._heads(self._lin(h, p["ctx"][n]))
                      for n in ("k", "v"))
        o = _sdpa(q, jnp.concatenate([kx, kc], axis=1),
                  jnp.concatenate([vx, vc], axis=1), causal=False)
        return self._post(x, p["x"], o, mx)

    def _cond(self, params, t, pooled):
        """c = MLP_t(sincos(1000 t)) + MLP_p(pooled), float32 [B, d]."""
        f32 = jnp.float32

        def mlp(p, h):
            h = jax.nn.silu(h @ p["w1"].astype(f32) + p["b1"].astype(f32))
            return h @ p["w2"].astype(f32) + p["b2"].astype(f32)

        B = pooled.shape[0]
        t = jnp.broadcast_to(jnp.asarray(t, f32), (B,))
        temb = timestep_embedding(1000.0 * t, self.cfg.time_embed_dim)
        return mlp(params["t_embed"], temb) \
            + mlp(params["pooled_embed"], pooled.astype(f32))

    # ------------------------------------------------------------ forward
    def denoise(self, params, z, t, cond):
        """z [B, N, latent_dim] patch tokens (N a square), t scalar or
        [B], cond ``{"seq": [B, T, ctx_dim], "pooled": [B, pooled_dim]}``
        -> the flow velocity [B, N, latent_dim], float32."""
        cfg = self.cfg
        dt = cfg.dtype
        n = z.shape[1]
        side = math.isqrt(n)
        if side * side != n:
            raise ValueError(f"{n} image tokens are no square patch grid")
        c = self._cond(params, t, cond["pooled"])
        x = (self._lin(z.astype(dt), params["x_embed"])
             + pos_table(cfg, side).astype(dt))
        with jax.named_scope("context_stream"):
            ctx = self._lin(cond["seq"].astype(dt), params["ctx_embed"])

        def body(carry, p):
            return self._joint(*carry, p, c), None

        (x, ctx), _ = jax.lax.scan(body, (x, ctx), params["blocks"])
        x = self._last(x, ctx, params["last"], c)
        sc, sh = self._mod(c, params["final"]["adaln"], 2)
        x = self._modulate(x, sh, sc)
        return self._lin(x, params["final"]["out"]).astype(jnp.float32)
