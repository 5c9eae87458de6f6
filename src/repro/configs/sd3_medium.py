"""SD3 Medium's MMDiT [arXiv:2403.03206;
stabilityai/stable-diffusion-3-medium-diffusers, transformer/config.json].

24 joint blocks of width 1536 (24 heads of 64, MLP 6144), patch 2 over a
128x128x16 latent at 1024 px (4096 image tokens of 64 values), text as
154 tokens of width 4096 (77 CLIP-L/G and 77 T5-XXL, no mask) and a
pooled vector of width 2048; ``pos_embed_max_size`` 192, no QK norm. The
network predicts rectified flow's velocity. Departures from the
published model: none in the equations (``models/mmdit.py``); the
pre-only last block holds no query projection, whose output the
published model discards.
"""

from . import ArchMeta
from ..models import MMDiTConfig

META = ArchMeta(
    name="sd3-medium",
    family="denoiser",
    shapes=(),
    source="arXiv:2403.03206; stabilityai/stable-diffusion-3-medium-"
           "diffusers transformer/config.json",
    notes="two-stream joint attention over 4096 image + 154 text tokens",
)


def full() -> MMDiTConfig:
    return MMDiTConfig(
        name="sd3-medium",
        n_layers=24,
        d_model=1536,
        n_heads=24,
        head_dim=64,
        d_ff=6144,
        patch_size=2,
        in_channels=16,
        sample_size=128,
        ctx_dim=4096,
        pooled_dim=2048,
        pos_embed_max_size=192,
    )


def smoke() -> MMDiTConfig:
    return MMDiTConfig(
        name="sd3-smoke",
        n_layers=2,
        d_model=64,
        n_heads=4,
        head_dim=16,
        d_ff=256,
        patch_size=2,
        in_channels=4,
        sample_size=8,
        ctx_dim=32,
        pooled_dim=24,
        pos_embed_max_size=12,
    )
