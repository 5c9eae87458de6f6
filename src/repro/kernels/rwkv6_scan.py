"""RWKV6 WKV recurrence as a chunked TPU kernel.

Grid = (B, H, n_chunks), chunk innermost; the per-head state S [hd, hd]
lives in VMEM scratch and persists across the chunk loop, so the HBM
traffic is exactly: read r/k/v/logw once, write y once, plus one [hd,hd]
state read/write per (b, h) — the recurrence itself never touches HBM.
(The naive sequential scan re-reads S from HBM every token: 2*T*hd*hd
bytes per head; the chunked kernel reduces state traffic by a factor of T.)

Intra-chunk math mirrors models.rwkv6.wkv_chunked: pairwise decayed dot
products with exponents L_{t-1} - L_s <= 0 (overflow-safe by construction),
then two MXU matmuls (A @ v and the state update k_dec^T @ v) per chunk.

VMEM at C=64, hd=64 (f32): r/k/v/logw 4x16 KiB, pairwise tensor
[C, C, hd] = 1 MiB, state 16 KiB — comfortably resident.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_wkv"]


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, y_ref, sout_ref,
            s_scr, *, chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)      # [C, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)         # [hd]

    L = jnp.cumsum(lw, axis=0)               # inclusive
    Lprev = L - lw
    Ltot = L[-1]                             # [hd]

    # pairwise decayed scores  A[t,s] = sum_i r[t,i] k[s,i] e^{Lprev_t - L_s}
    D = Lprev[:, None, :] - L[None, :, :]    # [C, C, hd], <= 0 for s < t
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = t_idx > s_idx                      # strict lower
    A = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(D), axis=-1)
    A = jnp.where(tri, A, 0.0)
    diag = jnp.sum(r * u[None, :] * k, axis=-1)          # bonus term [C]

    S = s_scr[...]
    y = jax.lax.dot_general(A, v, (((1,), (0,)), ((), ()))) \
        + diag[:, None] * v \
        + jax.lax.dot_general(r * jnp.exp(Lprev), S, (((1,), (0,)), ((), ())))
    y_ref[0, 0] = y.astype(y_ref.dtype)

    k_dec = k * jnp.exp(Ltot[None, :] - L)
    s_scr[...] = jnp.exp(Ltot)[:, None] * S \
        + jax.lax.dot_general(k_dec, v, (((0,), (0,)), ((), ())))

    @pl.when(c == nc - 1)
    def _fin():
        sout_ref[0, 0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv(r, k, v, logw, u, S0, *, chunk: int = 64,
              interpret: bool | None = None):
    """r/k/v/logw [B,T,H,hd]; u [H,hd]; S0 [B,H,hd,hd].
    Returns (y [B,T,H,hd] f32, S_T [B,H,hd,hd] f32).

    ``interpret=None`` auto-detects the backend like ``sa_update``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, hd = r.shape
    if T % chunk:
        raise ValueError(f"T={T} % chunk={chunk} != 0")
    nc = T // chunk
    # [B,T,H,hd] -> [B,H,T,hd] for contiguous chunk blocks
    tr = lambda a: jnp.swapaxes(a, 1, 2)
    grid = (B, H, nc)
    bspec = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    y, s_out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            bspec, bspec, bspec, bspec,
            pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),           # u
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),  # S0
        ],
        out_specs=[
            bspec,
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(tr(r), tr(k), tr(v), tr(logw), u, S0)
    return tr(y), s_out
