"""Fused dual-output SA-Solver combine: predictor + corrector partial sum
in ONE pass over the operands.

The PEC-with-corrector step evaluates two linear combinations that share
every operand:

    x_pred    = decay * x + sum_j p_j * buf[j] + noise * xi     (predictor)
    corr_base = decay * x + sum_j c_j * buf[j] + noise * xi     (corrector,
                                                   sans the new-eval term)

Run separately they read x, xi and the P buffer rows from HBM twice; this
kernel reads each operand tile once, keeps two f32 accumulators in VREGs,
and writes both outputs — (P+2) reads + 2 writes instead of 2(P+2) reads
+ 2 writes, roughly halving per-step solver HBM bytes. After the model
evaluation the corrector completes with a single pointwise
``corr_base + c_new * e_new``, touching only ``e_new`` — so the
post-eval corrector never re-reads the history.

Coefficients arrive as one f32 matrix [2, P+2], each row packed in the
``sa_update`` convention (decay, noise, b_0..b_{P-1}); row 0 is the
predictor, row 1 the corrector. With a ring-buffer history the caller
rotates the *coefficient columns* by the ring head — the [P, N] data is
never rotated or re-stacked (see ``samplers/multistep.py``).

Layout and tiling are ``sa_update``'s (the same kernel with two
coefficient rows): ``[n/128, 128]`` rows in sublane-aligned blocks, so the
kernel compiles under the serving lanes' ``vmap`` and scan-step calls are
copy-free.
"""

from __future__ import annotations

from .sa_update import DEFAULT_TILE, sa_combine

__all__ = ["sa_fused_update"]


def sa_fused_update(x, buf, xi, coeffs, *, tile: int = DEFAULT_TILE,
                    interpret: bool | None = None):
    """x [*shape]; buf [P, *shape]; xi [*shape]; coeffs [2, P+2] f32,
    rows packed as (decay, noise, b_0..b_{P-1}). Returns
    ``(x_pred, corr_base)``, both with x.dtype.

    ``interpret=None`` auto-detects the backend like ``sa_update``.
    """
    return sa_combine(x, buf, xi, coeffs, tile=tile, interpret=interpret)
