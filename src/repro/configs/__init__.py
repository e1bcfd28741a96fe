"""Architecture registry (``--arch <id>``) + assigned input shapes."""
from __future__ import annotations

import dataclasses
from typing import Dict, Union

from repro.configs import base
from repro.configs.base import DiTConfig, ModelConfig, MoEConfig, SSMConfig

from repro.configs import (command_r_plus_104b, deepseek_coder_33b, dit_small,
                           flux1_dev, flux1_kontext_dev, granite_moe_3b,
                           jamba_15_large,
                           llama3_405b, llava_next_34b, mamba2_370m,
                           phi35_moe_42b, seamless_m4t_medium, yi_9b)

_MODULES = [mamba2_370m, deepseek_coder_33b, seamless_m4t_medium,
            phi35_moe_42b, granite_moe_3b, llama3_405b, yi_9b,
            jamba_15_large, command_r_plus_104b, llava_next_34b,
            dit_small, flux1_dev, flux1_kontext_dev]

REGISTRY: Dict[str, Union[ModelConfig, DiTConfig]] = {
    m.CONFIG.arch_id: m.CONFIG for m in _MODULES
}

# the ten assigned (architecture x shape) targets
ASSIGNED = [
    "mamba2-370m", "deepseek-coder-33b", "seamless-m4t-medium",
    "phi3.5-moe-42b-a6.6b", "granite-moe-3b-a800m", "llama3-405b",
    "yi-9b", "jamba-1.5-large-398b", "command-r-plus-104b",
    "llava-next-34b",
]

INPUT_SHAPES = {
    "train_4k": {"seq_len": 4096, "global_batch": 256, "kind": "train"},
    "prefill_32k": {"seq_len": 32768, "global_batch": 32,
                    "kind": "prefill"},
    "decode_32k": {"seq_len": 32768, "global_batch": 128,
                   "kind": "decode"},
    "long_500k": {"seq_len": 524288, "global_batch": 1, "kind": "decode"},
}

# window used for the sliding-window carve-out at long_500k on pure
# full-attention architectures (DESIGN.md §4)
LONG_CONTEXT_WINDOW = 8192


def get_config(arch_id: str):
    return REGISTRY[arch_id]


def list_archs():
    return list(REGISTRY)


def needs_sliding_window(cfg: ModelConfig, shape_name: str) -> bool:
    """True when this (arch, shape) runs the sliding-window variant."""
    if shape_name != "long_500k":
        return False
    # SSM state is O(1); hybrid keeps its sparse 1:7 attention full.
    return cfg.family not in ("ssm", "hybrid")


def for_shape(cfg: ModelConfig, shape_name: str) -> ModelConfig:
    """Config variant actually lowered for a given input shape."""
    if isinstance(cfg, DiTConfig):
        return cfg
    updates = {}
    if needs_sliding_window(cfg, shape_name):
        updates["sliding_window"] = LONG_CONTEXT_WINDOW
    if INPUT_SHAPES[shape_name]["kind"] != "train":
        updates["remat"] = False
    return dataclasses.replace(cfg, **updates) if updates else cfg


def reduced(cfg):
    """CPU-runnable smoke variant of the same family (assignment: 2 layers,
    d_model <= 512, <= 4 experts)."""
    if isinstance(cfg, DiTConfig):
        return dataclasses.replace(
            cfg, n_layers=2, n_double=min(cfg.n_double, 1), d_model=64,
            n_heads=4, d_ff=128, text_dim=min(cfg.text_dim, 32),
            n_text_tokens=min(cfg.n_text_tokens, 8),
            vec_in_dim=min(cfg.vec_in_dim, 16),
            rope_axes=(4, 6, 6) if cfg.rope_axes else (), dtype="float32")
    n_layers = 2 if cfg.family != "hybrid" else cfg.attn_every
    d_model = 128
    head_dim = 32
    n_heads = d_model // head_dim
    moe = None
    if cfg.moe is not None:
        moe = dataclasses.replace(cfg.moe, n_experts=4,
                                  top_k=min(cfg.moe.top_k, 2))
    ssm = None
    if cfg.ssm is not None:
        ssm = dataclasses.replace(cfg.ssm, d_state=16, head_dim=32, chunk=16)
    return dataclasses.replace(
        cfg, n_layers=n_layers, d_model=d_model, n_heads=n_heads,
        n_kv_heads=max(1, n_heads // 2), d_ff=0 if cfg.d_ff == 0 else 256,
        vocab_size=512, head_dim=head_dim, moe=moe, ssm=ssm,
        n_enc_layers=min(cfg.n_enc_layers, 2),
        n_prefix_tokens=16 if cfg.n_prefix_tokens else 0,
        sliding_window=0, dtype="float32", remat=False)
