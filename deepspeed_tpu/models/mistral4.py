"""Mistral-Small-4: latent attention (MLA) in every layer — low-rank query and
key/value projections, a rotary part beside a part without, YaRN rotary
tables over interleaved pairs — and an expert layer with a sigmoid router, a
selection bias and one ungated shared expert as every layer's feed-forward
part.

Served, not trained: the paged programs run it (``inference/v2``: a chunk
expands keys and values from the cached latents, a decode row absorbs the
up-projections and attends the latents themselves); the training entry names
what is missing.  ``moe_held_first`` / ``moe_held_count`` and ``vocab_size``
make it one chip's share of an expert-parallel deployment
(``benchmark/configs/mistral-small4-119b-ep8-serve.json``).  The vision tower
is no part of it.
"""

from __future__ import annotations

from typing import Optional

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads, q_lora, kv_lora, qk_nope, qk_rope, v_dim,
    #        vocab, experts, top_k, expert_width, rope_factor, original_max)
    "tiny": (64, 4, 4, 32, 32, 16, 8, 16, 256, 8, 2, 32, 8.0, 16),
    "119b": (4096, 36, 32, 1024, 256, 64, 64, 128, 131072, 128, 4, 2048,
             128.0, 8192),
}


def mistral4_config(size: str = "119b", max_seq_len: int = 16384,
                    **overrides) -> TransformerConfig:
    (h, l, nh, ql, kvl, dn, dr, dv, vocab, experts, top_k, ew, factor,
     original) = SIZES[size]
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        head_dim_override=dn + dr, intermediate_size=ew,
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="none", norm_eps=1e-6, layer_period=("mla",),
        q_lora_rank=ql, kv_lora_rank=kvl, qk_nope_head_dim=dn,
        qk_rope_head_dim=dr, v_head_dim=dv, rope_theta=10000.0,
        rope_factor=factor, rope_original_max=original, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale_all_dim=1.0, attn_scale_beta=0.1,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=1.0, moe_shared_expert=ew, moe_shared_gate=False)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "mistral4 is served only: training it needs the latent form in the "
        "training forward (models/layer_types.py: 'mla' has no mix), and no "
        "cut of this family inside the floors fits a chip at 16 B a "
        "parameter (8 experts a layer x 4 layers are 16.3 GB); it is no "
        "kernel that is missing")


def mistral4_model(size: str = "119b", max_seq_len: int = 16384,
                   config: Optional[TransformerConfig] = None,
                   **overrides) -> ModelSpec:
    cfg = config or mistral4_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
