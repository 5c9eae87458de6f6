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
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

from ..kernels.flash_attention import flash_attention
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


#: shortest sequence at which the blocked kernel beats the jnp path on a
#: TPU v5e: it wins at 512 and 1024 tokens and loses at 256 (DiT-XL/2
#: layers, PERF.md)
KERNEL_MIN_LEN = 512


def use_kernel(backend: str, q_len: int, kv_len: int, cached: bool,
               softcap, causal: bool) -> bool:
    """Whether attention over ``q_len`` queries and ``kv_len`` keys runs
    as the blocked Pallas kernel: on TPU, without a KV cache or a logit
    softcap, at lengths where the chip shows it winning. The length
    threshold and the kernel's block sizes were measured on a TPU v5e at
    DiT-XL/2's shapes only (non-causal, 16 heads of 72), so causal (LM)
    attention keeps the jnp path until a cell measures it. Whether the
    program spans one device is known only when it is lowered
    (``_kernel_p``)."""
    return (backend == "tpu" and not cached and softcap is None
            and not causal and min(q_len, kv_len) >= KERNEL_MIN_LEN)


def _sdpa(q, k, v, *, causal: bool, q_offset=0, kv_len=None, softcap=None,
          q_chunk: int = 256, wo=None):
    """q [B,S,H,hd]; k,v [B,T,K,hd] -> [B,S,H,hd]. Dispatcher: the blocked
    Pallas kernel where ``use_kernel`` says so (``_kernel_attention``),
    else ``_attention_jnp``. Either runs under ``named_scope("attention")``,
    which names its ops in a profile.

    With ``wo`` [H,hd,d] it returns the output projection [B,S,d], outside
    the scope, in the form that suits the path taken: one [H*hd, d]
    matmul of the kernel's merged-head output (a per-head product would
    relayout it), and the per-head einsum on the jnp path (a merged one
    made XLA relayout the values product: step cell p50 +4-6%, PERF.md)."""
    kernel = use_kernel(jax.default_backend(), q.shape[1], k.shape[1],
                        kv_len is not None, softcap, causal)
    with jax.named_scope("attention"):
        if kernel:
            out = _kernel_attention(q, k, v, causal)
        else:
            out = _attention_jnp(q, k, v, causal=causal, q_offset=q_offset,
                                 kv_len=kv_len, softcap=softcap,
                                 q_chunk=q_chunk)
    if wo is None:
        return out
    if kernel:
        return jnp.einsum("bsn,nd->bsd", out.reshape(*out.shape[:2], -1),
                          wo.reshape(-1, wo.shape[-1]))
    return jnp.einsum("bshk,hkd->bsd", out, wo)


def _attention_jnp(q, k, v, *, causal: bool, q_offset=0, kv_len=None,
                   softcap=None, q_chunk: int = 256):
    """q-chunked via lax.map for long sequences (bounds live attention
    scores to [B,H,q_chunk,T]; XLA frees each chunk before the next
    because lax.map is sequential), direct otherwise."""
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


def _kernel_call(q, k, v, *, causal: bool):
    """The Pallas kernel in the model's layout, with bfloat16 operands
    (what the MXU takes for every default-precision dot of the backbone)
    and float32 softmax."""
    return flash_attention(q, k, v, causal=causal,
                           dtype=jnp.bfloat16).astype(q.dtype)


def _one_device(axis_context) -> bool:
    """Whether a program lowered in ``axis_context`` runs each instance on
    one device: XLA cannot partition a Pallas call, so it may appear only
    in a one-device program or where every mesh axis is manual (inside
    ``shard_map``)."""
    mesh = getattr(axis_context, "mesh", None)
    if mesh is not None:
        manual = set(axis_context.manual_axes) | set(
            getattr(mesh, "manual_axes", ()))
        return manual >= set(mesh.axis_names)
    return getattr(axis_context, "num_devices", 1) == 1


def _lower_kernel_p(ctx, q, k, v, *, causal):
    # decided here, where the program's devices are known: the kernel in
    # a one-device program, the jnp path where GSPMD partitions it
    # (serving's request or CFG axis over a mesh, training on a slice)
    fn = _kernel_call if _one_device(ctx.module_context.axis_context) \
        else _attention_jnp
    fn = _over_lead(functools.partial(fn, causal=causal), ctx.avals_in[0].ndim)
    return mlir.lower_fun(fn, multiple_results=False)(ctx, q, k, v)


def _over_lead(fn, ndim):
    """``fn`` of [B,S,H,hd] arrays, vmapped over the leading axes that
    batching put before B."""
    for _ in range(ndim - 4):
        fn = jax.vmap(fn)
    return fn


def _batch_kernel_p(args, dims, *, causal):
    # a vmapped axis (the serving lanes, the CFG pair) leads; the lowering
    # vmaps the kernel over it, so no reshape meets the projections' layout
    n = next(a.shape[d] for a, d in zip(args, dims)
             if d is not batching.not_mapped)
    args = [jnp.broadcast_to(a, (n,) + a.shape) if d is batching.not_mapped
            else jnp.moveaxis(a, d, 0) for a, d in zip(args, dims)]
    return _kernel_p.bind(*args, causal=causal), 0


#: the kernel as one primitive, so that the one-device question is
#: answered at lowering
_kernel_p = Primitive("attention_kernel")
_kernel_p.def_impl(
    lambda q, k, v, *, causal: _over_lead(
        functools.partial(_kernel_call, causal=causal), q.ndim)(q, k, v))
_kernel_p.def_abstract_eval(lambda q, k, v, *, causal: q)
mlir.register_lowering(_kernel_p, _lower_kernel_p)
batching.primitive_batchers[_kernel_p] = _batch_kernel_p


@functools.partial(jax.custom_jvp, nondiff_argnums=(3,))
def _kernel_attention(q, k, v, causal):
    """q [B,S,H,hd]; k,v [B,T,K,hd] -> [B,S,H,hd] through the kernel.
    The kernel has no derivative of its own: tangents, and by transposing
    them gradients, are the jnp path's at the same inputs."""
    return _kernel_p.bind(q, k, v, causal=causal)


@_kernel_attention.defjvp
def _kernel_attention_jvp(causal, primals, tangents):
    _, out_dot = jax.jvp(functools.partial(_attention_jnp, causal=causal),
                         primals, tangents)
    return _kernel_attention(*primals, causal), out_dot


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
        y = _sdpa(q, k, v, causal=causal, softcap=cfg.attn_logit_softcap,
                  wo=p["wo"])
    else:
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0)
        )
        cache = {"k": ck, "v": cv}
        y = _sdpa(
            q, ck, cv, causal=causal, q_offset=cache_index,
            kv_len=cache_index + S, softcap=cfg.attn_logit_softcap,
            wo=p["wo"],
        )
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
