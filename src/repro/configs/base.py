"""Config dataclasses shared by every architecture.

One ``ModelConfig`` covers all assigned families (dense / moe / ssm /
hybrid / audio enc-dec / vlm); ``DiTConfig`` covers the paper's own
diffusion-transformer denoisers.  Configs are plain frozen dataclasses so
they hash and can key jit caches.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # Apply an MoE FFN every `every` layers (1 = all layers, 2 = alternating).
    every: int = 1
    aux_loss_weight: float = 0.01
    router_z_weight: float = 1e-3
    # dispatch implementation: "einsum" (GShard one-hot matmul, the
    # baseline) or "gather" (slot-indexed gather/scatter, §Perf)
    impl: str = "einsum"
    # pad the expert count (never-routed zero-prob experts) so the
    # expert dim divides the TP axis -> expert parallelism instead of
    # d_ff-sharded experts with per-expert all-reduces (§Perf)
    padded_experts: int = 0

    @property
    def e_total(self) -> int:
        return max(self.n_experts, self.padded_experts)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    chunk: int = 256
    conv_width: int = 4
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 128
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid: one attention layer per `attn_every` layers (Jamba 1:7 -> 8).
    attn_every: int = 0
    # encoder-decoder (audio): encoder layer count; encoder consumes
    # precomputed frame embeddings from the (stubbed) modality frontend.
    is_encdec: bool = False
    n_enc_layers: int = 0
    # vlm: number of prefix embedding tokens supplied by the (stubbed)
    # vision frontend (anyres tiling already applied upstream).
    n_prefix_tokens: int = 0
    sliding_window: int = 0          # 0 = full attention
    rope_theta: float = 500000.0
    use_bias: bool = False
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    source: str = ""                 # citation for the config

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        ssm = self.ssm or SSMConfig()
        return ssm.expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        ssm = self.ssm or SSMConfig()
        return self.d_inner // ssm.head_dim

    def layer_kinds(self) -> Tuple[str, ...]:
        """Sequence of per-layer block kinds ('attn'|'ssm') of length n_layers."""
        if self.family == "ssm":
            return ("ssm",) * self.n_layers
        if self.family == "hybrid" and self.attn_every > 0:
            kinds = []
            for i in range(self.n_layers):
                # one attention layer at the end of every group of attn_every
                kinds.append("attn" if (i % self.attn_every) == self.attn_every - 1 else "ssm")
            return tuple(kinds)
        return ("attn",) * self.n_layers

    def is_moe_layer(self, layer_idx: int) -> bool:
        if self.moe is None or self.moe.n_experts == 0:
            return False
        return (layer_idx % self.moe.every) == (self.moe.every - 1)


@dataclasses.dataclass(frozen=True)
class DiTConfig:
    """Diffusion-transformer denoiser (the paper's model family).

    ``backbone`` may name an assigned ModelConfig arch to wrap as a
    denoiser (AdaLN time conditioning around its residual stack) — this is
    how FreqCa exercises the assigned architectures (DESIGN.md §4).
    """
    arch_id: str
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int
    patch_size: int = 2
    in_channels: int = 4
    # FLUX-like MMDiT: n_double joint (text+image dual-stream) blocks then
    # n_layers single-stream blocks. n_double == 0 -> plain DiT.
    n_double: int = 0
    text_dim: int = 0
    n_text_tokens: int = 0
    time_embed_dim: int = 256
    norm_eps: float = 1e-6
    dtype: str = "float32"
    source: str = ""
    # FLUX's conditioning vector: ``vec`` is the time embedding plus, when
    # set, an MLP embedding of a pooled text vector of ``vec_in_dim``
    # (CLIP, 768) and of the guidance scale (guidance-distilled models).
    # 0 / False: the time embedding alone, as a plain DiT.
    vec_in_dim: int = 0
    guidance_embed: bool = False
    # FLUX's ``EmbedND``: per-axis RoPE widths over (index, row, column)
    # token ids, summing to the head size, applied to q and k.  Empty:
    # 1-D sincos positions added to the image tokens.
    rope_axes: Tuple[int, ...] = ()
    rope_theta: float = 10000.0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads
