"""FLUX.1-Kontext-dev — FLUX.1-dev's in-context image editor [Labs 2025].

The widths of FLUX.1-dev (``configs["flux-dev-kontext"]`` in
github.com/black-forest-labs/flux, ``src/flux/util.py``): 19
dual-stream + 38 single-stream blocks, d=3072, 24 heads of 128, 512 T5
text tokens of 4096, a pooled CLIP vector of 768, guidance embedding,
3-axis RoPE (16, 56, 56) at theta 10000.  A request's reference image
joins the generated tokens with RoPE index 1 (``prepare_kontext``).
"""
from repro.configs.base import DiTConfig

CONFIG = DiTConfig(
    arch_id="flux1-kontext-dev", n_layers=38, n_double=19, d_model=3072,
    n_heads=24, d_ff=12288, patch_size=2, in_channels=16,
    text_dim=4096, n_text_tokens=512, vec_in_dim=768, guidance_embed=True,
    rope_axes=(16, 56, 56), rope_theta=10000.0, dtype="bfloat16",
    source="FLUX.1-Kontext-dev [github.com/black-forest-labs/flux]",
)
