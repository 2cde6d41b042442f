"""The paper's own model family: latent diffusion transformers.

Two serving models mirroring the paper's evaluation:
  * ``dit-image``  — Qwen-Image-analogue image DiT (paper §6.1)
  * ``dit-video``  — Wan2.2-5B-analogue video DiT  (paper §6.1)
and FLUX.1-dev (``flux1-dev``) at its published widths and depth.

Request classes (paper §6.1):
  Wan2.2  S/M/L: 480x832x49f / 480x832x81f / 720x1280x81f videos
  Qwen-Image S/M/L: 512/1024/1536 px images
"""
from repro.configs.base import DiTConfig, FULL, ModelConfig

# Image DiT — MM-DiT-style backbone sized near Qwen-Image-lite scale.
DIT_IMAGE = ModelConfig(
    name="dit-image",
    family="dit",
    num_layers=28,
    d_model=1536,
    num_heads=24,
    num_kv_heads=24,
    head_dim=64,
    d_ff=6144,
    vocab_size=0,
    attention=FULL,
    dit=DiTConfig(patch_size=2, in_channels=16, cond_dim=1024, num_steps=50),
)

# Video DiT — Wan-style 3D-latent backbone.
DIT_VIDEO = ModelConfig(
    name="dit-video",
    family="dit",
    num_layers=30,
    d_model=3072,
    num_heads=24,
    num_kv_heads=24,
    head_dim=128,
    d_ff=12288,
    vocab_size=0,
    attention=FULL,
    dit=DiTConfig(patch_size=2, in_channels=16, cond_dim=1024, num_steps=50,
                  latent_frames=21),
)

# FLUX.1-dev (black-forest-labs/FLUX.1-dev, transformer/config.json): 19
# double-stream and 38 single-stream blocks of width 3072 (24 heads of
# 128), GELU(tanh) MLP ratio 4, 3-axis RoPE (16, 56, 56) at theta 1e4,
# T5 text (4096 wide, 512 tokens), a pooled CLIP vector of 768, the
# guidance scale as an input.  The 16 latent channels of an 8x VAE
# patched 2 x 2 are FLUX's packed 64 input channels.  The flow shift is
# FLUX's resolution-dependent one at 4,096 tokens, e^1.15.
FLUX1_DEV = ModelConfig(
    name="flux1-dev",
    family="dit",
    num_layers=19,
    d_model=3072,
    num_heads=24,
    num_kv_heads=24,
    head_dim=128,
    d_ff=12288,
    vocab_size=0,
    attention=FULL,
    rope_theta=10000.0,
    dit=DiTConfig(patch_size=2, in_channels=16, cond_dim=4096, num_steps=50,
                  blocks="flux", num_single_layers=38, guidance_embeds=True,
                  pooled_dim=768, rope_axes=(16, 56, 56), text_len=512,
                  flow_shift=3.1582),
)
