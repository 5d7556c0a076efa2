"""SDAR-MoE: a Qwen3-MoE block (grouped-query attention with an RMSNorm on
every q and k head before rotary, softmax-routed experts as every layer's
feed-forward part, no shared expert) that generates by diffusion over blocks:
positions come in blocks of ``block_length`` under a mask causal between
blocks and bidirectional inside one, and a block is denoised in passes from
``mask_token_id`` (``inference/v2/block_diffusion.py``).

Served, not trained: the paged programs run it (``inference/v2``); the
training entry names what is missing.  ``n_layers`` makes it one stage of a
pipeline (``benchmark/configs/sdar-30b-a3b-pp8-serve.json``): every expert and
the whole vocabulary are held (``moe_held_count`` = ``moe_experts``, so the
expert layer is the share's path, with its counters, over all of them).
"""

from __future__ import annotations

from typing import Optional

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads, kv_heads, head_dim, vocab, experts,
    #        top_k, expert_width, mask_token_id)
    "tiny": (64, 2, 4, 2, 16, 256, 8, 2, 32, 255),
    "30b-a3b": (2048, 48, 32, 4, 128, 151936, 128, 8, 768, 151669),
}
#: the block length the family's chat checkpoints without a ``-b<N>`` suffix
#: were released with (the published config has no key for it)
BLOCK_LENGTH = 4


def sdar_moe_config(size: str = "30b-a3b", max_seq_len: int = 32768,
                    **overrides) -> TransformerConfig:
    h, l, nh, kvh, hd, vocab, experts, top_k, ew, mask_id = SIZES[size]
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, head_dim_override=hd, intermediate_size=ew,
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="rope", rope_theta=1e6, norm_eps=1e-6, tie_embeddings=False,
        qk_norm=True, moe_experts=experts, moe_top_k=top_k,
        moe_norm_topk=True, moe_scoring="softmax", moe_drop_tokens=False,
        moe_held_first=0, moe_held_count=experts,
        block_length=BLOCK_LENGTH, mask_token_id=mask_id)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "sdar_moe is served only: training it needs the block-diffusion "
        "objective (a noised copy of each block beside the clean sequence "
        "under a two-part mask, the loss at the masked positions alone), "
        "which does not exist; its expert layers would train as lfm2_moe's do")


def sdar_moe_model(size: str = "30b-a3b", max_seq_len: int = 32768,
                   config: Optional[TransformerConfig] = None,
                   **overrides) -> ModelSpec:
    cfg = config or sdar_moe_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
