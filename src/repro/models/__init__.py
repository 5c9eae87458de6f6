"""Model zoo: dense/MoE/MLA transformers, RWKV6, Mamba2/Zamba2 hybrid, and
the two-stream MMDiT denoiser (SD3).

All models share a duck-typed API:
    param_defs() -> ParamDef tree (stacked [L, ...] for scanned layers)
    loss_fn(params, batch) -> scalar
    forward(params, batch) -> (logits, aux)
    cache_shapes(batch, s_max) / init_cache(batch, s_max)
    prefill(params, batch, cache) -> (last_logits, cache)
    decode_step(params, tokens, cache, index) -> (logits, cache)
    denoise(params, z, t) -> x0-hat            (when denoiser mode enabled)

``build_model(cfg)`` dispatches on config type.
"""

from .attention import AttentionConfig, MLAConfig
from .common import ParamDef, abstract_params, init_params, specs_for
from .mamba2 import Mamba2Config, Zamba2, Zamba2Config
from .mmdit import MMDiT, MMDiTConfig
from .moe import MoEConfig
from .rwkv6 import RWKV6, RWKV6Config
from .transformer import LMConfig, TransformerLM

__all__ = [
    "AttentionConfig", "MLAConfig", "MoEConfig", "LMConfig", "TransformerLM",
    "RWKV6", "RWKV6Config", "Mamba2Config", "Zamba2", "Zamba2Config",
    "MMDiT", "MMDiTConfig",
    "ParamDef", "init_params", "abstract_params", "specs_for", "build_model",
]


def build_model(cfg):
    if isinstance(cfg, LMConfig):
        return TransformerLM(cfg)
    if isinstance(cfg, RWKV6Config):
        return RWKV6(cfg)
    if isinstance(cfg, Zamba2Config):
        return Zamba2(cfg)
    if isinstance(cfg, MMDiTConfig):
        return MMDiT(cfg)
    raise TypeError(f"unknown config type {type(cfg).__name__}")
