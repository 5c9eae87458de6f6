"""Assigned-architecture configs (one module per arch) + the paper's own.

Each module exposes
    full()   -> exact published config (assignment block)
    smoke()  -> reduced same-family config for CPU smoke tests
    META     -> ArchMeta (family, applicable shape cells, notes)

``get_config(name)`` / ``get_smoke(name)`` / ``ARCHS`` are the public API.

Shape cells (assignment):
    train_4k     seq 4096,   global_batch 256   (train_step)
    prefill_32k  seq 32768,  global_batch 32    (prefill)
    decode_32k   KV 32768,   global_batch 128   (serve_step, 1 new token)
    long_500k    KV 524288,  global_batch 1     (serve_step; sub-quadratic
                                                 archs only)
"""

from __future__ import annotations

import dataclasses
import importlib

__all__ = ["ARCHS", "SHAPES", "ArchMeta", "get_config", "get_smoke", "get_meta"]


@dataclasses.dataclass(frozen=True)
class ArchMeta:
    name: str
    family: str               # dense | moe | ssm | hybrid | audio | vlm
    shapes: tuple[str, ...]   # applicable cells
    source: str
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k": ShapeCell("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
    # the paper's own workload: SA-Solver sampling of the DiT denoisers
    # (seq = latent tokens; NFE-20 P3C3 tau=1 loop per launch/cells.py)
    "sample_256": ShapeCell("sample_256", 256, 256, "sample"),
    "sample_64": ShapeCell("sample_64", 64, 256, "sample"),
}

ARCHS = (
    "granite-34b",
    "starcoder2-15b",
    "starcoder2-3b",
    "gemma-7b",
    "musicgen-large",
    "rwkv6-3b",
    "qwen2-vl-2b",
    "deepseek-v3-671b",
    "dbrx-132b",
    "zamba2-7b",
    # the paper's own denoiser architectures
    "dit-xl-2",
    "dit-s",
    # the joint two-stream denoiser of Stable Diffusion 3
    "sd3-medium",
)

_MODULES = {name: name.replace("-", "_") for name in ARCHS}


def _mod(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_MODULES)}")
    return importlib.import_module(f".{_MODULES[name]}", __package__)


def get_config(name: str):
    return _mod(name).full()


def get_smoke(name: str):
    return _mod(name).smoke()


def get_meta(name: str) -> ArchMeta:
    return _mod(name).META
