"""Production mesh construction.

Target: TPU v5e pods. Single pod = 256 chips as (data=16, model=16);
multi-pod = 2 pods = 512 chips as (pod=2, data=16, model=16), where the
"pod" axis crosses the inter-pod DCN/ICI boundary (collectives over "pod"
are the expensive ones — batch/gradient only, never layer-internal TP).

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only dryrun.py
forces 512 host platform devices).
"""

from __future__ import annotations

import math

import jax
import numpy as np

__all__ = ["auto_mesh_of", "make_production_mesh", "make_test_mesh"]


def auto_mesh_of(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``. Since JAX 0.9 the
    default is ``Explicit`` axes, on which ``with_sharding_constraint``
    (sharded CFG) and GSPMD propagation are refused; the serving and
    production meshes rely on both."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devs)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "(launch/dryrun.py sets this)"
        )
    return auto_mesh_of(shape, axes, devs[:need])


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for in-container multi-device tests (8 fake devices)."""
    need = math.prod(shape)
    return auto_mesh_of(shape, axes, jax.devices()[:need])
