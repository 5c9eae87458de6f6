"""Fused SA-Solver state update (the paper's per-step hot spot).

    x' = decay * x + sum_{j<P} b_j * buf[j] + noise * xi

On GPU reference implementations this is a chain of P+2 pointwise kernels,
each reading/writing the full latent from HBM (2(P+2) HBM passes). The TPU
kernel fuses the whole combine: per VMEM tile it reads x, xi and the P
stacked buffer rows once, accumulates in f32 VREGs, writes once —
(P+2) reads + 1 write total, the HBM lower bound for this op. The MXU is
idle by design; the op is memory-bound and its roofline term is bytes.

Layout: the latent is flattened and viewed as ``[n/128, 128]`` rows of
one lane width; buffers stack to ``[P, n/128, 128]`` so the j-loop walks
VMEM, not HBM. A block is ``(tr, 128)`` rows with ``tr`` a multiple of the
dtype's sublane count (8 f32, 16 bf16) or all rows, so the last two block
dims are tile-aligned. Under ``jax.vmap`` (the serving lanes) Pallas adds a
leading grid axis and a squeezed leading block dim; the last two dims stay
aligned and the kernel still compiles. A latent whose size is not a
multiple of 128 is viewed as one row ``[1, n]`` tiled ``(1, t)`` with
``t`` a multiple of 128 (or all of n).

Coefficients arrive as one f32 matrix ``[R, P+2]``, each row packed
(decay, noise, b_0..b_{P-1}), broadcast to every tile (scalar traffic
only). R = 1 here; ``sa_fused`` runs the same kernel with R = 2.

Tiling: ``choose_tile`` picks the largest lane-aligned (multiple of
8*128 f32 / 16*128 bf16 elements) tile that *divides* n, so steady-state
steps are copy-free — no operand is ``jnp.pad``-ed inside the scan. When
n has no aligned divisor the requested tile is kept and the final grid
block is ragged: Pallas masks the out-of-bounds rows (reads see padding,
stores are dropped), still with zero host-side copies. Default TILE =
512*128 f32 elements (256 KiB per operand tile); with P=3 buffers the
working set is ~1.5 MiB << 16 MiB VMEM, letting the pipeliner
double-buffer the HBM streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["sa_update", "sa_combine", "choose_tile", "lane_align",
           "DEFAULT_TILE", "LANE_ALIGN"]

DEFAULT_TILE = 512 * 128
#: the TPU vreg lane width: the minor dim of every aligned block
LANES = 128
#: conservative lane-alignment unit for 1-D tiles: 16 sublanes x 128
#: lanes covers the minimum TPU tile for both f32 (8, 128) and bf16
#: (16, 128). Callers that know their dtype should prefer
#: ``lane_align(dtype)`` — at f32 it halves the alignment grain, so
#: twice as many latent sizes get an exactly-dividing (mask-free) tile.
LANE_ALIGN = 16 * 128
#: ragged (one-row) blocks pad each vreg to a full sublane tile, so their
#: VMEM cost is 8-16x the element count: keep them this small
RAGGED_TILE = 8 * 128


def lane_align(dtype) -> int:
    """Minimum lane-aligned 1-D tile unit for ``dtype``.

    TPU native tiles are (sublanes, 128) with the sublane count scaling
    inversely with element width — f32 (8, 128), bf16 (16, 128), int8
    (32, 128) — so the flattened-latent alignment unit is 1024 elements
    at f32 and 2048 at bf16: narrow history rows bank twice the elements
    per native tile.
    """
    bits = jnp.dtype(dtype).itemsize * 8
    return max(32 // bits, 1) * 8 * 128


def choose_tile(n: int, tile: int, align: int = LANE_ALIGN) -> int:
    """Largest ``align``-aligned tile <= ``tile`` that divides ``n``.

    ``align`` defaults to the dtype-agnostic ``LANE_ALIGN``; pass
    ``lane_align(dtype)`` for the exact per-dtype grain. Falls back to
    ``min(tile, n)`` when no aligned divisor exists — the grid then
    carries one ragged final block whose loads/stores Pallas masks
    automatically. Either way no operand is ever padded (copied) at the
    jnp level, so calling this inside a ``lax.scan`` step is copy-free
    in steady state. Divisors below ``tile // 8`` are not worth it (a
    tiny tile explodes the grid count and per-block overhead dominates —
    e.g. n = 2048 * large_prime would otherwise run thousands of
    2048-element blocks); the ragged masked path wins there.
    """
    t_max = min(tile, n)
    if n % t_max == 0:
        return t_max
    floor = max(align, (t_max // 8 // align) * align)
    t = (t_max // align) * align
    while t >= floor:
        if n % t == 0:
            return t
        t -= align
    return t_max  # ragged final block, masked by Pallas


def _blocks(n: int, tile: int, dtype) -> tuple[tuple[int, int],
                                                tuple[int, int]]:
    """(2-D view, block) of a flattened n-element latent.

    n % 128 == 0: ``[n/128, 128]`` in ``(tr, 128)`` blocks, ``tr`` from
    ``choose_tile`` and rounded to the dtype's sublane count unless it
    spans every row. Otherwise: ``[1, n]`` in ``(1, t)`` blocks with t a
    multiple of 128 no larger than ``RAGGED_TILE``, or all of n."""
    if n % LANES:
        t = min(n, max(LANES, min(tile, RAGGED_TILE) // LANES * LANES))
        return (1, n), (1, t)
    rows = n // LANES
    sub = lane_align(dtype) // LANES
    tr = max(choose_tile(n, tile, lane_align(dtype)) // LANES, 1)
    if tr < rows and tr % sub:
        tr = min(rows, max(sub, tr // sub * sub))
    return (rows, LANES), (tr, LANES)


def _kernel(coeff_ref, x_ref, buf_ref, xi_ref, *out_refs, P: int):
    x = x_ref[...].astype(jnp.float32)
    xi = xi_ref[...].astype(jnp.float32)
    accs = [coeff_ref[r, 0] * x + coeff_ref[r, 1] * xi
            for r in range(len(out_refs))]
    for j in range(P):  # unrolled: P is static and small (<= 5)
        bj = buf_ref[j].astype(jnp.float32)
        accs = [acc + coeff_ref[r, 2 + j] * bj
                for r, acc in enumerate(accs)]
    for acc, out_ref in zip(accs, out_refs):
        out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def sa_combine(x, buf, xi, coeffs, *, tile: int = DEFAULT_TILE,
               interpret: bool | None = None):
    """x [*shape]; buf [P, *shape]; xi [*shape]; coeffs [R, P+2] f32,
    rows packed (decay, noise, b_0..b_{P-1}). Returns a tuple of R
    combines, each with x.dtype — one pass over x, xi and buf for all R.

    ``interpret=None`` (default) auto-detects from the backend: compiled
    Mosaic on TPU, Python interpreter everywhere else (the correctness
    path for CPU containers). Pass an explicit bool to override.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = x.shape
    P = buf.shape[0]
    R = coeffs.shape[0]
    view, block = _blocks(x.size, tile, x.dtype)
    grid = (pl.cdiv(view[0], block[0]), pl.cdiv(view[1], block[1]))
    tile_spec = pl.BlockSpec(block, lambda i, j: (i, j))
    outs = pl.pallas_call(
        functools.partial(_kernel, P=P),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, P + 2), lambda i, j: (0, 0)),   # coeffs
            tile_spec,                                       # x tile
            pl.BlockSpec((P,) + block, lambda i, j: (0, i, j)),  # buffer
            tile_spec,                                       # xi tile
        ],
        out_specs=[tile_spec] * R,
        out_shape=[jax.ShapeDtypeStruct(view, x.dtype)] * R,
        interpret=interpret,
    )(coeffs.astype(jnp.float32), x.reshape(view), buf.reshape((P,) + view),
      xi.reshape(view))
    return tuple(o.reshape(shape) for o in outs)


def sa_update(x, buf, xi, coeffs, *, tile: int = DEFAULT_TILE,
              interpret: bool | None = None):
    """x [*shape]; buf [P, *shape]; xi [*shape]; coeffs [P+2] f32
    (decay, noise, b_0..b_{P-1}). Returns x' with x.dtype."""
    (out,) = sa_combine(x, buf, xi, coeffs[None], tile=tile,
                        interpret=interpret)
    return out
