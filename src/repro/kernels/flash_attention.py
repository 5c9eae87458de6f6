"""Blocked (flash) causal attention for 32k prefill.

Canonical TPU tiling: grid = (B, H, nQ, nK) with the KV index innermost;
running (max, sum, acc) live in VMEM scratch and persist across the nK
loop (TPU Pallas guarantees sequential grid iteration with the last axis
fastest). Per (q-block, k-block) step:

    s   = q @ k^T / sqrt(hd)      [BQ, BK]   (MXU)
    m'  = max(m, rowmax(s))
    acc = acc * exp(m - m') + exp(s - m') @ v   (MXU)

Causal blocks with j*BK > (i+1)*BQ - 1 contribute nothing; their work is
masked (grid-skip via index rewriting is a TPU-only optimization noted in
EXPERIMENTS.md §Perf — on average it halves the FLOPs; the masked version
keeps the kernel identical between interpret and compiled modes).

GQA: k/v carry K heads; the BlockSpec index_map sends q-head h to kv-head
h // (H // K), so no host-side broadcast materializes [B, H, T, hd].

Block sizes: BQ = BK = 512 with hd<=256 keeps q/k/v/acc tiles
(4 x 512 x 256 x 4B = 2 MiB) inside VMEM with double buffering; matmul
dims are multiples of 128 (MXU-aligned).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -2.0**30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bk: int, causal: bool, scale: float,
            kv_len: int | None):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # [BQ, hd]
    k = k_ref[0, 0].astype(jnp.float32)            # [BK, hd]
    v = v_ref[0, 0].astype(jnp.float32)            # [BK, hd]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [BQ,BK]

    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    if kv_len is not None:
        # ragged T: key positions past the true length are host-side
        # padding — knock them out of the softmax (static gate: the
        # divisible path traces the exact pre-ragged graph)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]                            # [BQ, 1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                         # [BQ, BK]
    alpha = jnp.exp(m_prev - m_new)                # [BQ, 1]
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha \
        + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(j == nk - 1)
    def _fin():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)) \
            .astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, bq: int = 512,
                    bk: int = 512, interpret: bool | None = None):
    """q [B,H,S,hd]; k,v [B,K,T,hd], K | H. Returns [B,H,S,hd] in q.dtype.

    ``interpret=None`` auto-detects the backend like ``sa_update``:
    compiled Mosaic on TPU, the Pallas interpreter elsewhere.

    Ragged (non-block-multiple) S/T are handled by zero-padding up to the
    block grid and masking: padded key positions get ``NEG_INF`` scores
    inside the kernel (so they never touch the softmax) and padded query
    rows are sliced off the output. Block-multiple shapes skip the
    padding entirely and trace the exact unpadded graph.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, S, hd = q.shape
    K, T = k.shape[1], k.shape[2]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, T)
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        pad = ((0, 0), (0, 0), (0, Tp - T), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    grid = (B, H, Sp // bq, Tp // bk)
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_kernel, bq=bq, bk=bk, causal=causal,
                               scale=scale,
                               kv_len=T if Tp != T else None)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            # (m, l, acc) persist across the innermost (nK) grid axis
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :S] if Sp != S else out
