"""jit'd public wrappers for the Pallas kernels with pure-jnp fallbacks.

Dispatch: ``use_pallas(mode)`` where mode in {"auto", "kernel", "jnp"}.
- "auto": kernel (interpret) on CPU only when explicitly benchmarked;
  model code defaults to the jnp path on CPU because interpret mode is a
  Python-loop emulator (correct, slow). On TPU "auto" means compiled
  kernels. The dry-run always lowers the jnp path (Mosaic does not lower
  on the CPU backend); kernel vs jnp numerical equivalence is asserted by
  tests, so the dry-run roofline is valid for both.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .rwkv6_scan import rwkv6_wkv as _wkv_kernel
from .sa_fused import sa_fused_update as _sa_fused_kernel
from .sa_update import sa_update as _sa_kernel

__all__ = ["sa_update", "sa_fused_update", "wkv", "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sa_update(x, buf, xi, coeffs, *, mode: str = "auto"):
    """coeffs [P+2] packed as (decay, noise, b_0..b_{P-1}) — one
    convention for the jnp oracle and the Pallas kernel alike."""
    if mode == "jnp" or (mode == "auto" and not on_tpu()):
        return ref.sa_update_ref(x, buf, xi, coeffs)
    return _sa_kernel(x, buf, xi, coeffs)  # interpret auto-detects backend


def sa_fused_update(x, buf, xi, coeffs, *, mode: str = "auto"):
    """Dual-output combine: coeffs [2, P+2] (rows packed like
    ``sa_update``; row 0 predictor, row 1 corrector) ->
    ``(x_pred, corr_base)``. One pass over x/xi/buf on TPU; the jnp
    oracle mirrors it with a single two-row contraction on CPU."""
    if mode == "jnp" or (mode == "auto" and not on_tpu()):
        return ref.sa_fused_update_ref(x, buf, xi, coeffs)
    return _sa_fused_kernel(x, buf, xi, coeffs)


def wkv(r, k, v, logw, u, S0, *, chunk: int = 64, mode: str = "auto"):
    if mode == "jnp" or (mode == "auto" and not on_tpu()):
        from ..models.rwkv6 import wkv_chunked
        return wkv_chunked(r, k, v, logw, u, S0, chunk)
    return _wkv_kernel(r, k, v, logw, u, S0, chunk=chunk)
