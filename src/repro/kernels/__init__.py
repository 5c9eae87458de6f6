"""Pallas TPU kernels for the perf-critical compute layers.

    sa_update.py        fused SA-Solver state update  (memory-bound)
    sa_fused.py         dual-output predictor+corrector combine (one pass)
    flash_attention.py  blocked attention, the DiT denoiser's on one TPU
                        chip (bidirectional; causal masking, tested)
    rwkv6_scan.py       chunked WKV recurrence        (state in VMEM)

Each kernel ships with a pure-jnp oracle in ``ref.py``; ``ops.py`` holds
the jit'd public wrappers with backend dispatch, except attention's, which
``models/attention._sdpa`` dispatches itself. On CPU the kernels
execute under ``interpret=True`` (Python emulation of the kernel
body) and tests assert allclose against the oracles over shape/dtype
sweeps; on TPU the same call sites compile through Mosaic.
"""

from . import ops, ref
from .flash_attention import flash_attention
from .rwkv6_scan import rwkv6_wkv
from .sa_fused import sa_fused_update
from .sa_update import sa_update

__all__ = ["ops", "ref", "sa_update", "sa_fused_update", "flash_attention",
           "rwkv6_wkv"]
