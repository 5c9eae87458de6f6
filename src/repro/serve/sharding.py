"""Mesh placement helpers for the serve engine.

The engine shards exactly one thing: the leading *request* axis of each
microbatch, over the ``data`` axis of a mesh from
``repro.launch.mesh.make_test_mesh`` / ``make_production_mesh``. Plan
arrays (coefficient tables) are replicated; the model axis is free for
the backbone's own tensor parallelism (``repro.models.common.specs_for``
with the ``serve_2d`` strategy). The actual ``NamedSharding`` placement
and the donated carry buffer live in
``repro.core.samplers.base.sample_sharded``; this module owns the
bucket-size arithmetic that makes batches divisible.
"""

from __future__ import annotations

from typing import Sequence

import jax

from ..launch.mesh import auto_mesh_of

__all__ = ["data_axis_size", "align_bucket_sizes", "auto_mesh",
           "auto_cfg_mesh"]


def data_axis_size(mesh, data_axis: str = "data") -> int:
    if data_axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {data_axis!r}; axes: {tuple(mesh.shape)}")
    return int(mesh.shape[data_axis])


def align_bucket_sizes(bucket_sizes: Sequence[int], n_data: int) -> tuple:
    """Round every bucket size up to a multiple of the data-axis size.

    ``NamedSharding`` needs the sharded axis divisible by the mesh axis;
    rounding *up* keeps every configured bucket usable (a too-small tail
    bucket just carries a few more masked pad lanes).
    """
    if n_data < 1:
        raise ValueError(f"data axis size must be >= 1, got {n_data}")
    aligned = sorted({-(-b // n_data) * n_data for b in bucket_sizes})
    return tuple(aligned)


def auto_mesh(data_axis: str = "data"):
    """A serving mesh over all visible devices: ``(data=n, model=1)``.

    Raises on a single device: a caller that asked for sharding must not
    silently get the unsharded path (pass ``mesh=None`` to the engine for
    that). Real deployments pass an explicit mesh
    (``make_production_mesh``) so the model axis is sized for the
    backbone's tensor parallelism instead.
    """
    n = len(jax.devices())
    if n <= 1:
        raise ValueError(
            f"a sharded serving mesh needs >= 2 devices, have {n} "
            f"({jax.devices()[0].platform})")
    return auto_mesh_of((n, 1), (data_axis, "model"), jax.devices())


def auto_cfg_mesh(data_axis: str = "data", cfg_axis: str = "cfg"):
    """A CFG-factored serving mesh: ``(cfg=2, data=n//2)``.

    Sharded classifier-free guidance places the cond/uncond pair on the
    size-2 ``cfg`` axis — each device evaluates ONE branch at the local
    batch instead of both at a doubled local batch — and the request
    axis on the remaining ``data`` factor. Raises when there are fewer
    than two (or an odd number of) devices; without a mesh the engine
    runs the fused doubled-lane eval, which is numerically the same
    combine.
    """
    n = len(jax.devices())
    if n < 2 or n % 2:
        raise ValueError(
            f"a CFG-sharded mesh needs an even device count >= 2, have {n} "
            f"({jax.devices()[0].platform})")
    return auto_mesh_of((2, n // 2), (cfg_axis, data_axis), jax.devices())
