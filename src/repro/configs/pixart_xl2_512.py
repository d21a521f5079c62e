"""pixart-xl2-512 — PixArt-alpha XL/2 at 512 px, a text-to-image latent
diffusion transformer [Chen et al. 2023, arXiv:2310.00426; the checkpoint
PixArt-alpha/PixArt-XL-2-512x512]. DiT-XL/2's widths (28 blocks, d_model
1152, 16 heads of 72, MLP 4608, GELU-tanh) with adaLN-single time
conditioning and a cross-attention sub-block per block over a T5-XXL
caption of 120 tokens of 4096. Patch 2 on the 64x64x4 latent: 1024 tokens
of 16. LayerNorm eps 1e-6."""

from .base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        arch_id="pixart-xl2-512", family="dit", source="arXiv:2310.00426",
        num_layers=28, d_model=1152, num_heads=16, num_kv_heads=16,
        d_ff=4608, vocab_size=0, act="gelu", norm="layernorm",
        qkv_bias=True,
        latent_dim=16, patch_tokens=1024, caption_tokens=120,
        caption_dim=4096, norm_eps=1e-6,
    )
