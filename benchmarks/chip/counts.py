"""Operations and bytes from shapes, and the chips' published peaks.

These are the benchmark's own arithmetic: the program under test never
supplies a count. A configuration file (``configs/*.json``) gives the
sizes and its family module (``families/<name>.py``) the FLOPs of one
forward; a request gives the latent tokens and the solver's NFE.
"""

from __future__ import annotations

#: Published peaks per chip, keyed by ``jax.Device.device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip)",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row for a device kind; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def sample_flops(forward_flops: int, nfe: int, guided: bool) -> int:
    """Model FLOPs of one served sample: NFE guided evaluations, each one
    forward (of ``forward_flops``) per branch (two under classifier-free
    guidance)."""
    return nfe * (2 if guided else 1) * forward_flops


def solver_step_bytes(tokens: int, latent_dim: int, history: int,
                      itemsize: int = 4) -> int:
    """Least HBM bytes of one multistep update of one request: read the
    state, the step's noise, ``history`` past evaluations and the new
    evaluation; write the predicted state and the corrected state. The
    new evaluation enters the history where the network wrote it, so no
    copy is counted. The same whichever combine runs."""
    n = tokens * latent_dim
    return (history + 3 + 2) * n * itemsize
