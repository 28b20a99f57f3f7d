"""pixart-alpha [diffusion] — PixArt-α XL/2 (Chen et al. 2023,
arXiv:2310.00426; checkpoint ``PixArt-alpha/PixArt-XL-2-512x512``): a
text-to-image latent transformer.  Each of its 28 blocks self-attends over
the image tokens, cross-attends to 120 T5-XXL caption tokens (width 4096,
through a caption projection) and runs a GELU MLP, modulated by
adaLN-single; sampling uses classifier-free guidance at 4.5 against a
learned null caption.

At 512x512: a 64x64x4 latent patchified at p=2 => 1024 tokens of
latent_dim=16; the output has 2 x 4 channels a pixel (eps and the learned
sigma).  The T5 encoder is not part of the denoiser: requests carry its
output, a (120, 4096) caption and a token count.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="pixart-alpha",
    family="diffusion",
    num_layers=28,
    d_model=1152,
    num_heads=16,
    num_kv_heads=16,
    head_dim=72,
    d_ff=4608,
    vocab_size=0,
    act="gelu",
    is_diffusion=True,
    latent_dim=16,              # 2x2 patch of 4-channel latents
    num_tokens=1024,            # (64 / 2)^2 patches of the 64x64 latent
    latent_channels=4,
    caption_len=120,            # T5 max_length
    caption_dim=4096,           # T5-XXL width
    guidance_scale=4.5,
    tp_strategy="heads",
)
