"""Decoder-only transformer family (dense + MoE + MLA) with scan-over-layers.

One class covers granite / starcoder2 / gemma / musicgen / qwen2-vl /
deepseek-v3 / dbrx by config. Entry points (uniform across the zoo):

    param_defs()                      -> ParamDef tree (stacked [L, ...])
    loss_fn(params, batch)            -> scalar LM loss (+ MoE aux, + MTP)
    forward(params, tokens|embeds)    -> logits
    prefill(params, batch, cache)     -> (last_logits, cache)
    decode_step(params, tokens, cache, index) -> (logits, cache)
    cache_shapes(batch, s_max)        -> ShapeDtypeStruct tree
    denoise(params, z, t)             -> x0-prediction (denoiser mode)

Layer parameters carry a leading [L] axis and the stack is applied with a
single ``lax.scan`` so compiled HLO size is O(1) in depth (critical for the
88-layer configs in the 512-device dry-run). ``remat`` selects the
activation-checkpoint policy applied to the scanned block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .attention import AttentionConfig, MLAConfig, attn_defs, cache_shape, gqa_forward, mla_forward
from .common import (ParamDef, chunked_lm_loss, mlp_apply, mlp_defs, rms_norm,
                     shard_batch_dim, shard_logits_path, softmax_cross_entropy)
from .moe import MoEConfig, moe_apply, moe_defs

__all__ = ["LMConfig", "TransformerLM"]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str = "lm"
    family: str = "dense"  # dense | moe | audio | vlm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    head_dim: int | None = None  # default d_model // n_heads
    d_ff: int = 1024
    vocab_size: int = 1024
    act: str = "silu"
    gated_mlp: bool = True
    rope_theta: float = 10000.0
    rope_type: str = "rope"  # rope | mrope | none
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    tie_embeddings: bool = False
    embed_scale: bool = False  # gemma: scale embeddings by sqrt(d)
    attn_logit_softcap: float | None = None
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    n_dense_layers: int = 0   # deepseek: first k layers dense even in MoE nets
    mtp: bool = False         # deepseek multi-token prediction module
    mtp_weight: float = 0.3
    # input mode: "tokens" (default) or "embeds" (audio/vlm stub frontends)
    input_mode: str = "tokens"
    remat: str = "none"  # none | full | dots
    dtype: Any = jnp.bfloat16
    cache_dtype: Any = jnp.bfloat16
    # denoiser mode (SA-Solver integration): adds time-conditioned
    # continuous-latent input/output heads and disables the causal mask.
    denoiser_latent: int | None = None
    # width of the optional conditioning vector (class embedding / text
    # pooled embedding) mixed into the adaLN signal; None = unconditional
    denoiser_cond: int | None = None

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def attn_config(self) -> AttentionConfig:
        return AttentionConfig(
            d_model=self.d_model, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, head_dim=self.hd,
            rope_theta=self.rope_theta, rope_type=self.rope_type,
            mrope_sections=self.mrope_sections, causal=True, mla=self.mla,
            attn_logit_softcap=self.attn_logit_softcap,
        )

    def param_count(self) -> tuple[int, int]:
        """(total, active) parameter counts, analytic."""
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab_size
        if self.mla is not None:
            m = self.mla
            qk = m.qk_nope_dim + m.qk_rope_dim
            attn = (d * m.q_lora_rank + m.q_lora_rank * self.n_heads * qk
                    + d * (m.kv_lora_rank + m.qk_rope_dim)
                    + m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_dim)
                    + self.n_heads * m.v_dim * d)
        else:
            attn = d * self.n_heads * self.hd * 2 + d * self.n_kv_heads * self.hd * 2
        mlp_mats = 3 if self.gated_mlp else 2
        dense_mlp = mlp_mats * d * f
        if self.moe is not None:
            mo = self.moe
            expert = mlp_mats * d * mo.d_expert_ff
            shared = (mlp_mats * d * mo.d_shared_ff) if mo.n_shared else 0
            router = d * mo.n_experts
            n_moe = L - self.n_dense_layers
            total_mlp = (self.n_dense_layers * dense_mlp
                         + n_moe * (expert * mo.n_experts + shared + router))
            active_mlp = (self.n_dense_layers * dense_mlp
                          + n_moe * (expert * mo.top_k + shared + router))
        else:
            total_mlp = active_mlp = L * dense_mlp
        emb = V * d * (1 if self.tie_embeddings else 2)
        total = L * attn + total_mlp + emb
        active = L * attn + active_mlp + emb
        return total, active


def _remat_policy(name: str):
    if name == "none":
        return None
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def timestep_embedding(t, dim: int, max_period: float = 10000.0):
    """Sinusoidal embedding of (possibly batched) scalar t."""
    t = jnp.atleast_1d(jnp.asarray(t, jnp.float32))
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    ang = t[..., None] * freqs
    return jnp.concatenate([jnp.cos(ang), jnp.sin(ang)], axis=-1)


class TransformerLM:
    def __init__(self, cfg: LMConfig):
        self.cfg = cfg
        self.acfg = cfg.attn_config()

    # ------------------------------------------------------------------
    # parameters
    # ------------------------------------------------------------------
    def _block_defs(self, moe_layer: bool) -> dict:
        cfg = self.cfg
        d = {
            "ln1": ParamDef((cfg.d_model,), (None,), "zeros"),
            "ln2": ParamDef((cfg.d_model,), (None,), "zeros"),
            "attn": attn_defs(self.acfg),
        }
        if moe_layer:
            d["moe"] = moe_defs(cfg.d_model, cfg.moe)
        else:
            d["mlp"] = mlp_defs(cfg.d_model, cfg.d_ff, cfg.gated_mlp)
        if cfg.denoiser_latent is not None:
            d["adaln"] = ParamDef((cfg.d_model, 6 * cfg.d_model), ("embed", None), "zeros")
        return d

    @staticmethod
    def _stack(defs: dict, n: int) -> dict:
        return jax.tree.map(
            lambda pd: ParamDef((n,) + pd.shape, (None,) + pd.axes, pd.init, pd.scale),
            defs, is_leaf=lambda x: isinstance(x, ParamDef),
        )

    def param_defs(self) -> dict:
        cfg = self.cfg
        n_moe = cfg.n_layers - cfg.n_dense_layers if cfg.moe else 0
        n_dense = cfg.n_layers - n_moe
        out: dict = {
            "embed": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                              "normal", 0.02),
            "ln_f": ParamDef((cfg.d_model,), (None,), "zeros"),
        }
        if n_dense:
            out["blocks"] = self._stack(self._block_defs(moe_layer=False), n_dense)
        if n_moe:
            out["moe_blocks"] = self._stack(self._block_defs(moe_layer=True), n_moe)
        if not cfg.tie_embeddings:
            out["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                      ("embed", "vocab"), "scaled")
        if cfg.mtp:
            out["mtp"] = {
                "proj": ParamDef((2 * cfg.d_model, cfg.d_model), ("embed", None), "scaled"),
                "block": self._block_defs(moe_layer=False),
                "ln": ParamDef((cfg.d_model,), (None,), "zeros"),
            }
        if cfg.denoiser_latent is not None:
            dz = cfg.denoiser_latent
            out["denoiser"] = {
                "in_proj": ParamDef((dz, cfg.d_model), (None, "embed"), "scaled"),
                "out_proj": ParamDef((cfg.d_model, dz), ("embed", None), "zeros"),
                "t_mlp1": ParamDef((256, cfg.d_model), (None, "embed"), "scaled"),
                "t_mlp2": ParamDef((cfg.d_model, cfg.d_model), ("embed", None), "scaled"),
            }
            if cfg.denoiser_cond is not None:
                out["denoiser"]["y_proj"] = ParamDef(
                    (cfg.denoiser_cond, cfg.d_model), (None, "embed"), "scaled")
        return out

    # ------------------------------------------------------------------
    # blocks
    # ------------------------------------------------------------------
    def _attn(self, p, x, *, positions=None, cache=None, cache_index=None, causal=None):
        if self.cfg.mla is not None:
            return mla_forward(p, self.acfg, x, positions=positions, cache=cache,
                               cache_index=cache_index, causal=causal,
                               absorb=getattr(self, "mla_absorb", None))
        return gqa_forward(p, self.acfg, x, positions=positions, cache=cache,
                           cache_index=cache_index, causal=causal)

    def _block(self, p, x, *, moe_layer: bool, positions=None, cache=None,
               cache_index=None, causal=None, tcond=None):
        aux = jnp.zeros((), jnp.float32)
        if tcond is not None and "adaln" in p:
            mod = (tcond @ p["adaln"]).astype(jnp.float32)
            (s1, g1, b1, s2, g2, b2) = jnp.split(mod, 6, axis=-1)
            h = rms_norm(x, p["ln1"]) * (1 + s1[:, None, :]).astype(x.dtype) \
                + b1[:, None, :].astype(x.dtype)
            a, cache = self._attn(p["attn"], h, positions=positions,
                                  cache=cache, cache_index=cache_index,
                                  causal=causal)
            x = x + g1[:, None, :].astype(x.dtype) * a.astype(x.dtype)
            h = rms_norm(x, p["ln2"]) * (1 + s2[:, None, :]).astype(x.dtype) \
                + b2[:, None, :].astype(x.dtype)
            if moe_layer:
                m, aux = moe_apply(p["moe"], self.cfg.moe, h)
            else:
                m = mlp_apply(p["mlp"], h, self.cfg.act, self.cfg.gated_mlp)
            x = x + g2[:, None, :].astype(x.dtype) * m.astype(x.dtype)
            return x, cache, aux
        a, cache = self._attn(p["attn"], rms_norm(x, p["ln1"]), positions=positions,
                              cache=cache, cache_index=cache_index, causal=causal)
        x = x + a.astype(x.dtype)
        h = rms_norm(x, p["ln2"])
        if moe_layer:
            m, aux = moe_apply(p["moe"], self.cfg.moe, h)
        else:
            m = mlp_apply(p["mlp"], h, self.cfg.act, self.cfg.gated_mlp)
        return x + m.astype(x.dtype), cache, aux

    def _run_stack(self, params, x, *, positions=None, caches=None,
                   cache_index=None, causal=None, tcond=None):
        """Scan dense blocks then MoE blocks. caches: dict with stacked-layer
        trees under the same keys ('blocks', 'moe_blocks')."""
        cfg = self.cfg
        policy = _remat_policy(cfg.remat)
        total_aux = jnp.zeros((), jnp.float32)
        new_caches = {} if caches is not None else None

        for key, moe_layer in (("blocks", False), ("moe_blocks", True)):
            if key not in params:
                continue

            def body(carry, layer_in, _moe=moe_layer):
                xx, auxx = carry
                lp, lcache = layer_in
                xx = shard_batch_dim(xx)  # pin batch->data at layer boundary
                xx, lcache, a = self._block(
                    lp, xx, moe_layer=_moe, positions=positions, cache=lcache,
                    cache_index=cache_index, causal=causal, tcond=tcond,
                )
                return (xx, auxx + a), lcache

            if policy is not None:
                body = jax.checkpoint(body, policy=policy)
            # None is an empty pytree: scanning over (params, None) keeps the
            # per-layer cache argument None inside the body (training path).
            layer_caches = caches.get(key) if caches is not None else None
            (x, total_aux), out_caches = jax.lax.scan(
                body, (x, total_aux), (params[key], layer_caches)
            )
            if caches is not None:
                new_caches[key] = out_caches
        return x, new_caches, total_aux

    # ------------------------------------------------------------------
    # embedding / head
    # ------------------------------------------------------------------
    def _embed(self, params, batch):
        cfg = self.cfg
        if cfg.input_mode == "embeds" or "embeds" in batch:
            x = batch["embeds"].astype(cfg.dtype)
        else:
            x = params["embed"][batch["tokens"]].astype(cfg.dtype)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
        return x

    def _head_weight(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def _logits(self, params, x):
        h = rms_norm(x, params["ln_f"])
        h, _ = shard_logits_path(h, None)
        logits = (h @ self._head_weight(params).astype(h.dtype)).astype(jnp.float32)
        _, logits = shard_logits_path(None, logits)
        return logits

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def forward(self, params, batch):
        x = self._embed(params, batch)
        positions = batch.get("positions")
        x, _, aux = self._run_stack(params, x, positions=positions)
        return self._logits(params, x), aux

    def loss_fn(self, params, batch):
        """Causal LM loss; labels = batch['labels'] ([B, S], next-token).
        Large vocabularies go through the sequence-chunked head (bounds the
        live [B, chunk, V] logits; see common.chunked_lm_loss)."""
        cfg = self.cfg
        x = self._embed(params, batch)
        positions = batch.get("positions")
        x, _, aux = self._run_stack(params, x, positions=positions)
        S = x.shape[1]
        if cfg.vocab_size >= 32000 and S > 512 and S % 512 == 0:
            h = rms_norm(x, params["ln_f"])
            h, _ = shard_logits_path(h, None)
            loss = chunked_lm_loss(h, self._head_weight(params).astype(h.dtype),
                                   batch["labels"], batch.get("mask"))
        else:
            logits = self._logits(params, x)
            loss = softmax_cross_entropy(logits, batch["labels"],
                                         batch.get("mask"))
        if self.cfg.mtp and "labels2" in batch:
            # DeepSeek-V3 MTP: fuse trunk state with the embedding of the
            # next token, run one extra block, predict token t+2 with the
            # shared head.
            mp = params["mtp"]
            tgt_emb = params["embed"][batch["labels"]].astype(x.dtype)
            h = jnp.concatenate([x, tgt_emb], axis=-1) @ mp["proj"]
            h, _, _ = self._block(mp["block"], h, moe_layer=False,
                                  positions=positions)
            logits2 = self._logits(params, rms_norm(h, mp["ln"]))
            loss = loss + self.cfg.mtp_weight * softmax_cross_entropy(
                logits2, batch["labels2"], batch.get("mask")
            )
        return loss + aux

    # ---- serving ------------------------------------------------------
    def cache_shapes(self, batch: int, s_max: int) -> dict:
        cfg = self.cfg
        per_layer = cache_shape(self.acfg, batch, s_max, cfg.cache_dtype)
        out = {}
        n_moe = cfg.n_layers - cfg.n_dense_layers if cfg.moe else 0
        n_dense = cfg.n_layers - n_moe
        stack = lambda n: jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), per_layer
        )
        if n_dense:
            out["blocks"] = stack(n_dense)
        if n_moe:
            out["moe_blocks"] = stack(n_moe)
        return out

    def init_cache(self, batch: int, s_max: int) -> dict:
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.cache_shapes(batch, s_max)
        )

    def prefill(self, params, batch, cache):
        """Run the prompt, filling cache from position 0. Returns logits of
        the last position and the filled cache."""
        x = self._embed(params, batch)
        positions = batch.get("positions")
        x, cache, _ = self._run_stack(params, x, positions=positions,
                                      caches=cache, cache_index=0)
        return self._logits(params, x[:, -1:, :]), cache

    def decode_step(self, params, tokens, cache, index):
        """tokens [B, 1] (or embeds [B, 1, d]); index: scalar position."""
        batch = {"tokens": tokens} if tokens.ndim == 2 else {"embeds": tokens}
        x = self._embed(params, batch)
        x, cache, _ = self._run_stack(params, x, caches=cache, cache_index=index)
        return self._logits(params, x), cache

    # ---- denoiser mode (SA-Solver integration) ------------------------
    def _tcond(self, dp, t, batch: int, cond):
        """adaLN conditioning signal in f32 from an f32 ``t``: quantizing
        ``t`` (or the cond vector) to bf16 collapses adjacent solver
        timesteps. Its dots, ``tcond @ adaln`` too, run at default
        precision, which on the TPU rounds both operands to bf16."""
        t = jnp.broadcast_to(jnp.asarray(t, jnp.float32), (batch,))
        temb = timestep_embedding(t, 256)
        tcond = jax.nn.silu(temb @ dp["t_mlp1"].astype(jnp.float32)) \
            @ dp["t_mlp2"].astype(jnp.float32)
        if cond is not None:
            assert self.cfg.denoiser_cond is not None, \
                "conditioning input requires denoiser_cond in the config"
            c = jnp.asarray(cond, jnp.float32)
            c = jnp.broadcast_to(jnp.atleast_2d(c), (batch, c.shape[-1]))
            tcond = tcond + c @ dp["y_proj"].astype(jnp.float32)
        return tcond

    def denoise(self, params, z, t, cond=None):
        """z [B, S, dz], t scalar (or [B]) -> x0 prediction [B, S, dz].
        Bidirectional attention + adaLN time conditioning; ``cond``
        ([d_cond] or [B, d_cond]) joins ``t`` in the adaLN signal."""
        cfg = self.cfg
        assert cfg.denoiser_latent is not None, "build with denoiser_latent"
        dp = params["denoiser"]
        x = (z.astype(cfg.dtype) @ dp["in_proj"].astype(cfg.dtype))
        tcond = self._tcond(dp, t, z.shape[0], cond)
        x, _, _ = self._run_stack(params, x, causal=False, tcond=tcond)
        x = rms_norm(x, params["ln_f"])
        return (x @ dp["out_proj"].astype(cfg.dtype)).astype(jnp.float32)

    # ---- step-to-step feature caching (DeepCache-style) ---------------
    def cache_span(self) -> tuple[int, int]:
        """Default [a, b) mid-segment of the block stack to cache: the
        deep interior whose activations drift slowest across adjacent
        solver steps, keeping the shallow in/out layers (which track the
        changing latent) live. One-sixth of the depth on each side."""
        L = self.cfg.n_layers
        k = max(1, L // 6)
        return (k, L - k)

    def feature_shape(self, batch: int, seq: int) -> jax.ShapeDtypeStruct:
        """Aval of the cached mid-segment residual for one [batch, seq, dz]
        latent — the residual lives in the d_model stream."""
        return jax.ShapeDtypeStruct((batch, seq, self.cfg.d_model),
                                    self.cfg.dtype)

    def denoise_cached(self, params, z, t, cond=None, *, feats, refresh,
                       span=None):
        """``denoise`` with the mid-segment of the block stack either
        recomputed (``refresh``) or replaced by the cached residual delta
        ``feats`` (DeepCache: reuse deep activations across adjacent
        low-change solver steps). Returns ``(x0_prediction, new_feats)``.

        ``refresh`` may be a Python bool — specializing the graph, which
        is how the benchmarks compile the pure-cached variant for FLOP
        accounting — or a traced scalar bool (``lax.cond`` dispatch; note
        under ``vmap`` a batched predicate lowers to ``select`` and both
        branches are paid). ``span`` overrides :meth:`cache_span`. The
        cached quantity is the *residual* ``y - x`` across [a, b), so a
        refresh-every-step schedule reproduces ``denoise`` exactly.
        """
        cfg = self.cfg
        assert cfg.denoiser_latent is not None, "build with denoiser_latent"
        if "moe_blocks" in params:
            raise NotImplementedError(
                "feature caching requires a dense (non-MoE) block stack")
        a, b = self.cache_span() if span is None else span
        L = cfg.n_layers
        assert 0 <= a <= b <= L, f"bad cache span ({a}, {b}) for L={L}"
        dp = params["denoiser"]
        x = (z.astype(cfg.dtype) @ dp["in_proj"].astype(cfg.dtype))
        tcond = self._tcond(dp, t, z.shape[0], cond)

        def seg(lo, hi):
            return jax.tree.map(lambda p: p[lo:hi], params["blocks"])

        def run(blocks, xx):
            out, _, _ = self._run_stack({"blocks": blocks}, xx,
                                        causal=False, tcond=tcond)
            return out

        if a > 0:
            x = run(seg(0, a), x)

        def full(xx, old):
            y = run(seg(a, b), xx)
            return y, (y - xx).astype(old.dtype)

        def cached(xx, old):
            return xx + old.astype(xx.dtype), old

        if isinstance(refresh, bool):
            x, feats = (full if refresh else cached)(x, feats)
        else:
            x, feats = jax.lax.cond(refresh, full, cached, x, feats)
        if b < L:
            x = run(seg(b, L), x)
        x = rms_norm(x, params["ln_f"])
        out = (x @ dp["out_proj"].astype(cfg.dtype)).astype(jnp.float32)
        return out, feats

    def dot_weights(self) -> dict:
        """A bool for each leaf of ``param_defs()``: True for the layer
        stacks' matrices (adaLN, attention and MLP projections), which the
        blocks read only as operands of default-precision matmuls, and
        False for the norm gains and every leaf outside the stacks."""
        return {k: jax.tree.map(
                    lambda d, stack=k in ("blocks", "moe_blocks"):
                    stack and len(d.shape) >= 3,
                    v, is_leaf=lambda d: isinstance(d, ParamDef))
                for k, v in self.param_defs().items()}
