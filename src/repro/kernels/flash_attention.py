"""Blocked (flash) attention: the no-cache attention of the DiT denoiser
blocks on TPU, bidirectional (causal masking is kept, tested but not
dispatched: no cell measures LM prefill).

``models/attention._sdpa`` calls it on TPU for the no-cache, non-causal
path from 512 tokens, in a program that runs on one device (XLA cannot
partition a Pallas call), in the model's own layout (q [B,S,H,hd], k/v
[B,T,K,hd]), with bfloat16 operands; the MXU takes them with float32
accumulation, and the softmax statistics stay float32. The heads stay merged along the lane axis
([B, S, H*hd], a free reshape of the projections' output), so the
projections run at full MXU width and no transpose meets the kernel: one
grid step takes a q block of every head, and slices each head's columns
in VMEM. Grid = (B, nQ, nK) with the KV index innermost. Per head and
(q-block, k-block) step:

    s   = q @ k^T                [BQ, BK] (MXU; q carries 1/sqrt(hd))
    m'  = max(m, rowmax(s))
    acc = acc * exp(m - m') + exp(s - m') @ v   (MXU, p in the operand dtype)

and ``acc / l`` once at the end. Where one KV block spans T (``nK == 1``,
the DiT's 1024 tokens) the running statistics vanish: the kernel takes a
plain softmax of the tile and needs no scratch.

Causal blocks with j*BK > (i+1)*BQ - 1 contribute nothing; their work is
masked, not skipped, which keeps the kernel identical between interpret
and compiled modes.

GQA: k/v carry K heads; q head h reads kv head h // (H // K), so no
host-side broadcast materializes [B, T, H, hd].

Block sizes are a function of the shapes (``_block_sizes``), chosen by
sweeps on a TPU v5e at DiT-XL/2's shapes (16 heads of 72, non-causal,
256 and 1024 tokens) and at SD3 Medium's joint attention (24 heads of
64, 4096 image + 154 text tokens, a ragged 4250; PERF.md): one KV block
over every key wherever K and V fit in VMEM.
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
#: scoped VMEM the kernel may take: the heads' unrolled score tiles
#: overlap (a v5e core has 128 MiB; swept at DiT-XL/2's width)
_VMEM_LIMIT = 100 * 2**20
#: q rows x merged width of the largest q block
_Q_BLOCK_ELEMS = 512 * 1152
#: VMEM that one KV block over every key may take for K and V, each
#: double-buffered (SD3 Medium's 4250 x 1536 bf16 take 52 MB)
_KV_BLOCK_BYTES = 64 * 2**20
#: float32 elements of one head's score tile, bq x bk
_TILE_ELEMS = 1024 * 1024


def _block_sizes(S: int, T: int, width: int,
                 itemsize: int = 2) -> tuple[int, int]:
    """(bq, bk) for S queries over T keys with ``width`` = H * hd merged
    columns of ``itemsize``-byte operands: one KV block over all T keys
    (a plain softmax, no running statistics) while K and V, double-
    buffered, fit in ``_KV_BLOCK_BYTES``, else blocks of 512 keys; q
    blocks of up to 512 rows at the DiT's width, fewer where the heads
    are wider or a head's score tile would pass ``_TILE_ELEMS``.

    Measured on a TPU v5e, one layer over 8 sequences of 24 heads of 64
    (PERF.md): at SD3 Medium's joint 4250 tokens this gives (128, 4250)
    at 13.0-13.1 ms, against 13.8 at (256, 4250), which the score-tile
    cap refuses, no fit in VMEM at (512, 4250), 17.6 at (128, 2176),
    23.5 at (128, 1024) and 28.7 at (256, 512). At 5440 tokens, where K
    and V take 63.75 MiB, one block (23.9 ms) still beats 2720 (25.1)
    and 1024 (36.4). At 6144 (72 MiB) one block also won (27.4 against
    38.8 at 1024), so the KV bound errs low; the 512-key fallback past
    it was not timed. DiT-XL/2's 256 and 1024 tokens get (256, 256) and
    (512, 1024), as swept at their shapes."""
    bk = T if 4 * T * width * itemsize <= _KV_BLOCK_BYTES else 512
    rows = min(_Q_BLOCK_ELEMS // width, _TILE_ELEMS // bk)
    bq = S if S <= rows else max(128, 1 << (rows.bit_length() - 1))
    return bq, bk


def _mask(s, i, j, *, bq, bk, causal, kv_len):
    if causal:
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos <= qpos, s, NEG_INF)
    if kv_len is not None:
        # ragged T: key positions past the true length are host-side
        # padding — knock them out of the softmax (static gate: the
        # divisible path traces the exact pre-ragged graph)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
    return s


def _cols(h, hd):
    return slice(h * hd, (h + 1) * hd)


def _scores(q_ref, k_ref, h, kh, hd):
    return jax.lax.dot_general(
        q_ref[0, :, _cols(h, hd)], k_ref[0, :, _cols(kh, hd)],
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _pv(p, v_ref, kh, hd):
    v = v_ref[0, :, _cols(kh, hd)]
    return jax.lax.dot_general(p.astype(v.dtype), v,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel_one_block(q_ref, k_ref, v_ref, o_ref, *, heads, group, hd, bq,
                      bk, causal, kv_len):
    """nK == 1: each head's softmax over the whole tile, no scratch."""
    i = pl.program_id(1)
    for h in range(heads):
        s = _mask(_scores(q_ref, k_ref, h, h // group, hd), i, 0, bq=bq,
                  bk=bk, causal=causal, kv_len=kv_len)
        p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
        acc = _pv(p, v_ref, h // group, hd)
        o_ref[0, :, _cols(h, hd)] = \
            (acc / jnp.sum(p, axis=1, keepdims=True)).astype(o_ref.dtype)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, heads,
            group, hd, bq, bk, causal, kv_len):
    """nK > 1: online softmax; (m, l, acc) per head persist in VMEM
    across the innermost grid axis."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    for h in range(heads):
        s = _mask(_scores(q_ref, k_ref, h, h // group, hd), i, j, bq=bq,
                  bk=bk, causal=causal, kv_len=kv_len)
        m_prev = m_scr[h]                              # [BQ, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [BQ, BK]
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + _pv(p, v_ref, h // group, hd)
        m_scr[h] = m_new

    @pl.when(j == nk - 1)
    def _fin():
        for h in range(heads):
            o_ref[0, :, _cols(h, hd)] = \
                (acc_scr[h] / l_scr[h]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "dtype", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, dtype=None,
                    bq: int | None = None, bk: int | None = None,
                    interpret: bool | None = None):
    """q [B,S,H,hd]; k,v [B,T,K,hd], K | H. Returns [B,S,H,hd] in
    ``dtype``, the dots' operand type (q's when None); q is scaled by
    1/sqrt(hd) in float32 before it is cast. ``bq``/``bk`` default to
    ``_block_sizes`` of the shapes. ``interpret=None`` auto-detects the
    backend like ``sa_update``: compiled Mosaic on TPU, the Pallas
    interpreter elsewhere.

    Ragged (non-block-multiple) S/T are handled by zero-padding up to the
    block grid and masking: padded key positions get ``NEG_INF`` scores
    inside the kernel (so they never touch the softmax) and padded query
    rows are sliced off the output. Block-multiple shapes skip the
    padding entirely and trace the exact unpadded graph.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    dtype = dtype or q.dtype
    dq, dk = _block_sizes(S, T, H * hd, jnp.dtype(dtype).itemsize)
    bq = min(bq or dq, S)
    bk = min(bk or dk, T)
    q = (q.astype(jnp.float32) * (1.0 / math.sqrt(hd))).astype(dtype)
    q = q.reshape(B, S, H * hd)
    k = k.astype(dtype).reshape(B, T, K * hd)
    v = v.astype(dtype).reshape(B, T, K * hd)
    Sp = -(-S // bq) * bq
    Tp = -(-T // bk) * bk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        pad = ((0, 0), (0, Tp - T), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    nk = Tp // bk
    static = dict(heads=H, group=H // K, hd=hd, bq=bq, bk=bk, causal=causal,
                  kv_len=T if Tp != T else None)
    if nk == 1:
        kernel, scratch = functools.partial(_kernel_one_block, **static), []
    else:
        kernel = functools.partial(_kernel, **static)
        scratch = [pltpu.VMEM((H, bq, 1), jnp.float32),
                   pltpu.VMEM((H, bq, 1), jnp.float32),
                   pltpu.VMEM((H, bq, hd), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(B, Sp // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, H * hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, K * hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, K * hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, H * hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sp, H * hd), dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    out = out[:, :S] if Sp != S else out
    return out.reshape(B, S, H, hd)
