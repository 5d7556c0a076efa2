"""Solar-Open2: softmax GQA layers (no rotary, gated output) and delta-rule
linear-attention (KDA) layers in a period of four, an expert layer with one
shared expert as every layer's feed-forward part.

Served, not trained: the paged programs run it (``inference/v2``); the
training entry names what is missing.  ``moe_held_first`` / ``moe_held_count``
and ``vocab_size`` make it one chip's share of an expert-parallel deployment
(``benchmark/configs/solar-open2-250b-ep8-serve.json``).
"""

from __future__ import annotations

from typing import Optional

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads, kv_heads, head_dim, vocab, experts,
    #        top_k, expert_width, kda_heads, kda_head_dim, kda_rank)
    "tiny": (64, 4, 4, 2, 16, 256, 16, 4, 32, 4, 16, 8),
    "250b": (4096, 48, 64, 8, 128, 196608, 320, 8, 1280, 64, 128, 128),
}
PERIOD = ("attn", "kda", "kda", "kda")


def solar_open2_config(size: str = "250b", max_seq_len: int = 8192,
                       **overrides) -> TransformerConfig:
    (h, l, nh, kvh, hd, vocab, experts, top_k, ew, knh, khd,
     rank) = SIZES[size]
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, head_dim_override=hd, intermediate_size=ew,
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="none", norm_eps=1e-5, layer_period=PERIOD, attn_gate=True,
        kda_heads=knh, kda_head_dim=khd, kda_conv=4, kda_rank=rank,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=True,
        moe_shared_expert=ew, moe_shared_gate=False)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "solar_open2 is served only: training it needs the backward of the "
        "delta-rule scan (ops/pallas/kda.py: dstpu_kda_chunk), which does "
        "not exist; its expert layers would train as lfm2_moe's do")


def solar_open2_model(size: str = "250b", max_seq_len: int = 8192,
                      config: Optional[TransformerConfig] = None,
                      **overrides) -> ModelSpec:
    cfg = config or solar_open2_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
