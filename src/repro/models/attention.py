"""Attention variants: GQA/MQA/MHA with RoPE / M-RoPE, and DeepSeek MLA.

Cache layouts (per layer; stacked over layers by the caller):
    GQA : k, v           [B, S_max, K, hd]
    MLA : c_kv [B, S_max, kv_lora], k_rope [B, S_max, rope_dim]
MLA decode supports two paths: ``absorb=False`` (baseline: up-project the
whole cache each step) and ``absorb=True`` (weight-absorbed attention in the
compressed space — the production optimization; see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from .common import (ParamDef, apply_mrope, apply_rope, rms_norm,
                     shard_heads_dim)

NEG_INF = -2.0**30


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_type: str = "rope"  # "rope" | "mrope" | "none"
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    causal: bool = True
    mla: MLAConfig | None = None
    attn_logit_softcap: float | None = None
    #: route the no-cache path (causal LM prefill or bidirectional
    #: denoiser blocks) through kernels/flash_attention (jnp oracle on
    #: CPU, Mosaic kernel on TPU)
    use_flash: bool = False


# ---------------------------------------------------------------------------
# Parameter schemas
# ---------------------------------------------------------------------------


def attn_defs(cfg: AttentionConfig) -> dict:
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_dim + m.qk_rope_dim
        return {
            "wq_a": ParamDef((cfg.d_model, m.q_lora_rank), ("embed", None), "scaled"),
            "q_norm": ParamDef((m.q_lora_rank,), (None,), "zeros"),
            "wq_b": ParamDef((m.q_lora_rank, cfg.n_heads, qk), (None, "heads", None), "scaled"),
            "wkv_a": ParamDef((cfg.d_model, m.kv_lora_rank + m.qk_rope_dim), ("embed", None), "scaled"),
            "kv_norm": ParamDef((m.kv_lora_rank,), (None,), "zeros"),
            "wk_b": ParamDef((m.kv_lora_rank, cfg.n_heads, m.qk_nope_dim), (None, "heads", None), "scaled"),
            "wv_b": ParamDef((m.kv_lora_rank, cfg.n_heads, m.v_dim), (None, "heads", None), "scaled"),
            "wo": ParamDef((cfg.n_heads, m.v_dim, cfg.d_model), ("heads", None, "embed"), "scaled"),
        }
    H, K, hd, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    return {
        "wq": ParamDef((d, H, hd), ("embed", "heads", None), "scaled"),
        "wk": ParamDef((d, K, hd), ("embed", "kv_heads", None), "scaled"),
        "wv": ParamDef((d, K, hd), ("embed", "kv_heads", None), "scaled"),
        "wo": ParamDef((H, hd, d), ("heads", None, "embed"), "scaled"),
    }


def cache_shape(cfg: AttentionConfig, batch: int, s_max: int, dtype=jnp.bfloat16) -> dict:
    """ShapeDtypeStructs for a single layer's cache (caller stacks layers)."""
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jax.ShapeDtypeStruct((batch, s_max, m.kv_lora_rank), dtype),
            "k_rope": jax.ShapeDtypeStruct((batch, s_max, m.qk_rope_dim), dtype),
        }
    return {
        "k": jax.ShapeDtypeStruct((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jax.ShapeDtypeStruct((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------


def _sdpa(q, k, v, *, causal: bool, q_offset=0, kv_len=None, softcap=None,
          q_chunk: int = 256):
    """q [B,S,H,hd]; k,v [B,T,K,hd]. Dispatcher: q-chunked via lax.map for
    long sequences (bounds live attention scores to [B,H,q_chunk,T] —
    the jnp stand-in for the flash kernel's blocking; XLA frees each chunk
    before the next because lax.map is sequential), direct otherwise.
    The scores, softmax and weighted values run under
    ``named_scope("attention")``, which names their ops in a profile."""
    with jax.named_scope("attention"):
        B, S, H, hd = q.shape
        if S > q_chunk and S % q_chunk == 0:
            n = S // q_chunk
            qc = jnp.swapaxes(q.reshape(B, n, q_chunk, H, hd), 0, 1)
            offs = q_offset + jnp.arange(n) * q_chunk

            @jax.checkpoint
            def one(args):
                # checkpointed: map-backward saves only the chunk inputs, not
                # the [B,H,chunk,T] softmax residuals of every chunk at once
                qi, off = args
                return _sdpa_block(qi, k, v, causal=causal, q_offset=off,
                                   kv_len=kv_len, softcap=softcap)

            out = jax.lax.map(one, (qc, offs))
            return jnp.swapaxes(out, 0, 1).reshape(B, S, H, v.shape[-1])
        return _sdpa_block(q, k, v, causal=causal, q_offset=q_offset,
                           kv_len=kv_len, softcap=softcap)


def _sdpa_block(q, k, v, *, causal: bool, q_offset=0, kv_len=None, softcap=None):
    """q [B,S,H,hd]; k,v [B,T,K,hd] (K divides H). Returns [B,S,H,hd_v].

    ``kv_len``: number of valid cache positions (decode); positions >= kv_len
    are masked. ``q_offset``: absolute position of q[0] for causal masking.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores / math.sqrt(hd)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    tpos = jnp.arange(T)
    mask = None
    if causal:
        spos = jnp.arange(S) + q_offset
        mask = tpos[None, :] <= spos[:, None]  # [S, T]
    if kv_len is not None:
        valid = tpos < kv_len  # [T]
        mask = valid[None, :] if mask is None else (mask & valid[None, :])
    if mask is not None:
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(B, S, H, v.shape[-1]).astype(q.dtype)


def _positions(batch_shape, seq, offset):
    return jnp.arange(seq)[None, :] + offset


def _rope_q_or_k(cfg: AttentionConfig, x, positions):
    if cfg.rope_type == "rope":
        return apply_rope(x, positions, cfg.rope_theta)
    if cfg.rope_type == "mrope":
        return apply_mrope(x, positions, cfg.mrope_sections, cfg.rope_theta)
    return x


# ---------------------------------------------------------------------------
# GQA forward (train / prefill / decode)
# ---------------------------------------------------------------------------


def gqa_forward(
    p: dict,
    cfg: AttentionConfig,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    cache: dict | None = None,
    cache_index: jnp.ndarray | None = None,
    causal: bool | None = None,
) -> tuple[jnp.ndarray, dict | None]:
    """x [B,S,d]. Without cache: full self-attention (causal per cfg).
    With cache: writes k/v at cache_index..cache_index+S and attends over the
    cache (prefill S>1, decode S=1)."""
    B, S, _ = x.shape
    causal = cfg.causal if causal is None else causal
    offset = 0 if cache_index is None else cache_index
    if positions is None:
        positions = _positions((B,), S, offset)
        if cfg.rope_type == "mrope":
            # text-only default: all three M-RoPE streams share positions
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = _rope_q_or_k(cfg, q, positions)
    k = _rope_q_or_k(cfg, k, positions)
    # head-parallel attention internals (Megatron layout); the S-sharded
    # residual stream is gathered here and the heads dim takes over 'model'
    q = shard_heads_dim(q)
    k = shard_heads_dim(k)
    v = shard_heads_dim(v)

    if cache is None:
        if cfg.use_flash and cfg.attn_logit_softcap is None:
            from ..kernels import ops as kops
            o = kops.flash_attention(
                jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                jnp.swapaxes(v, 1, 2), causal=causal,
            )
            out = jnp.swapaxes(o, 1, 2)
        else:
            out = _sdpa(q, k, v, causal=causal, softcap=cfg.attn_logit_softcap)
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0)
        )
        cache = {"k": ck, "v": cv}
        out = _sdpa(
            q, ck, cv, causal=causal, q_offset=cache_index,
            kv_len=cache_index + S, softcap=cfg.attn_logit_softcap,
        )
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, cache


# ---------------------------------------------------------------------------
# MLA forward
# ---------------------------------------------------------------------------


def mla_forward(
    p: dict,
    cfg: AttentionConfig,
    x: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
    cache: dict | None = None,
    cache_index: jnp.ndarray | None = None,
    causal: bool | None = None,
    absorb: bool | None = None,
) -> tuple[jnp.ndarray, dict | None]:
    m = cfg.mla
    assert m is not None
    B, S, _ = x.shape
    if absorb is None:
        # decode (S=1): weight-absorbed attention in the compressed space —
        # expanding the cache to per-head K/V costs 2*T*r*H*(nope+v) FLOPs
        # and a [B,T,H,256] f32 materialization (34 GB/device for deepseek
        # decode_32k). prefill/train: expansion amortizes over S queries and
        # absorb would 4x the score FLOPs (r=512 vs nope=128), so expand.
        absorb = S == 1 and cache is not None
    causal = cfg.causal if causal is None else causal
    offset = 0 if cache_index is None else cache_index
    if positions is None:
        positions = _positions((B,), S, offset)

    q_lat = rms_norm(x @ p["wq_a"], p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
    q = shard_heads_dim(q)  # head-parallel MLA attention
    q_nope, q_rope = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., : m.kv_lora_rank], p["kv_norm"])  # [B,S,r]
    k_rope = apply_rope(kv[..., None, m.kv_lora_rank:], positions, cfg.rope_theta)[:, :, 0]

    if cache is not None:
        c_all = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, cache_index, 0)
        )
        kr_all = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), (0, cache_index, 0)
        )
        cache = {"c_kv": c_all, "k_rope": kr_all}
        kv_len = cache_index + S
    else:
        c_all, kr_all, kv_len = c_kv, k_rope, None

    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    T = c_all.shape[1]

    def _mask(s_len, off):
        tpos = jnp.arange(T)
        mk = None
        if causal:
            spos = jnp.arange(s_len) + off
            mk = tpos[None, :] <= spos[:, None]
        if kv_len is not None:
            valid = tpos < kv_len
            mk = valid[None, :] if mk is None else (mk & valid[None, :])
        return mk

    if absorb:
        # fold W_uk into q, attend in compressed space, fold W_uv after —
        # per-token score work drops from H*(nope+rope)*T reads of a
        # materialized [T, H, hd] K to (r + rope)*T reads of the cache.
        def attend(qn, qr, off):
            q_c = jnp.einsum("bshk,rhk->bshr", qn.astype(jnp.float32),
                             p["wk_b"].astype(jnp.float32))
            s_c = jnp.einsum("bshr,btr->bhst", q_c, c_all.astype(jnp.float32))
            s_r = jnp.einsum("bshk,btk->bhst", qr.astype(jnp.float32),
                             kr_all.astype(jnp.float32))
            scores = (s_c + s_r) * scale
            mk = _mask(qn.shape[1], off)
            if mk is not None:
                scores = jnp.where(mk[None, None], scores, NEG_INF)
            w = jax.nn.softmax(scores, axis=-1)
            o_c = jnp.einsum("bhst,btr->bshr", w, c_all.astype(jnp.float32))
            o = jnp.einsum("bshr,rhv->bshv", o_c, p["wv_b"].astype(jnp.float32))
            return o.astype(x.dtype)
    else:
        k_nope = jnp.einsum("btr,rhk->bthk", c_all, p["wk_b"])
        v = jnp.einsum("btr,rhv->bthv", c_all, p["wv_b"])
        # expanded K/V must be head-parallel: c_all is S-sharded over
        # 'model' and wk_b is head-sharded over 'model'; unconstrained,
        # GSPMD replicates heads (measured 4 GiB f32 [B,T,H,hd] blocks)
        k_nope = shard_heads_dim(k_nope)
        v = shard_heads_dim(v)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kr_all[:, :, None, :],
                                      k_nope.shape[:3] + (m.qk_rope_dim,))],
            axis=-1,
        )

        def attend(qn, qr, off):
            q_full = jnp.concatenate([qn, qr], axis=-1)
            scores = jnp.einsum("bshk,bthk->bhst", q_full.astype(jnp.float32),
                                k_full.astype(jnp.float32)) * scale
            mk = _mask(qn.shape[1], off)
            if mk is not None:
                scores = jnp.where(mk[None, None], scores, NEG_INF)
            w = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bhst,bthv->bshv", w,
                              v.astype(jnp.float32)).astype(x.dtype)

    q_chunk = 256
    if S > q_chunk and S % q_chunk == 0:
        # bound live [B,H,chunk,T] scores; lax.map is sequential so chunks
        # are freed (jnp stand-in for flash blocking)
        n = S // q_chunk
        resh = lambda a: jnp.swapaxes(
            a.reshape(B, n, q_chunk, *a.shape[2:]), 0, 1)
        offs = offset + jnp.arange(n) * q_chunk
        out = jax.lax.map(
            jax.checkpoint(lambda ar: attend(ar[0], ar[1], ar[2])),
            (resh(q_nope), resh(q_rope), offs))
        out = jnp.swapaxes(out, 0, 1).reshape(B, S, cfg.n_heads, -1)
    else:
        out = attend(q_nope, q_rope, offset)

    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, cache
