"""LFM2-MoE (LiquidAI ``lfm2_moe``): gated short-convolution layers and GQA
layers (RMSNorm on each q and k head, rotary) in the published order, a dense
SwiGLU feed-forward part in the first ``num_dense_layers`` layers and, in the
others, experts routed by a sigmoid with a per-expert selection bias.

Trained, not served: ``deepspeed_tpu.initialize(model=lfm2_moe_model(...))``
-> ``engine.train_batch``.  The stack is ``TransformerConfig.layer_types``
(``models/layer_types.py``); ``held_first`` / ``held_count`` and
``vocab_size`` make it one chip's share of an expert-parallel job
(``benchmark/configs/lfm2-8b-a1b-ep4-train.json``), whose expert layers run
the grouped Pallas matmuls forward and backward and return their counters
(``engine.moe_stats()``).  The selection bias is a buffer
(``ModelSpec.buffers``): drawn from the seed and left alone, since the rule
that updates it in the published training is not in the config.
"""

from __future__ import annotations

from typing import Optional, Sequence

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, causal_lm_loss, flops_per_token,
                          init_transformer_params, logits_fn,
                          transformer_forward, transformer_partition_rules)

#: HF ``layer_types`` names -> this program's layer types
HF_TYPES = {"conv": "conv", "full_attention": "attn"}
_PUBLISHED = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))

SIZES = {
    # name: (hidden, heads, kv_heads, head_dim, dense_ffn, expert_ffn, vocab,
    #        experts, top_k, dense_layers, layer_types)
    "tiny": (64, 4, 2, 16, 128, 32, 256, 8, 2, 1,
             ("conv", "full_attention", "conv", "conv", "conv")),
    "8b-a1b": (2048, 32, 8, 64, 7168, 1792, 65536, 32, 4, 2, _PUBLISHED),
}


def lfm2_moe_config(size: str = "8b-a1b", max_seq_len: int = 8192,
                    layer_types: Optional[Sequence[str]] = None,
                    **overrides) -> TransformerConfig:
    """``layer_types`` in the published names (``conv`` / ``full_attention``);
    ``num_dense_layers`` of them, from the first, are dense (override
    ``dense_layers``).  ``moe_held_first`` / ``moe_held_count``: the share."""
    (h, nh, kvh, hd, dense, ew, vocab, experts, top_k, n_dense,
     types) = SIZES[size]
    types = tuple(HF_TYPES[t] for t in (layer_types or types))
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=len(types), n_heads=nh,
        n_kv_heads=kvh, head_dim_override=hd, intermediate_size=ew,
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="rope", rope_theta=1e6, norm_eps=1e-5, tie_embeddings=True,
        qk_norm=True, layer_types=types, dense_layers=n_dense,
        dense_ffn_size=dense, conv_taps=3,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_router_bias=True, moe_routed_scale=1.0,
        moe_drop_tokens=False, moe_aux_coef=0.0)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _refuse_what_is_not_brought(cfg: TransformerConfig) -> None:
    from ..parallel.mesh import peek_topology

    topo = peek_topology()
    if topo is not None:
        wide = {a: topo.axis_size(a) for a in ("pipe", "model", "expert",
                                               "sequence")
                if topo.axis_size(a) > 1}
        if wide:
            raise NotImplementedError(
                f"lfm2_moe trains over data-parallel mesh axes only; this "
                f"mesh has {wide}: its expert share is one chip's (no "
                "exchange), its convolution and expert layers have no "
                "tensor-, sequence- or pipeline-parallel form yet")
    if cfg.moe_held_count and cfg.moe_drop_tokens:
        raise NotImplementedError(
            "lfm2_moe: an expert share (moe_held_count) is dropless; "
            "moe_drop_tokens=True has no form with a share")


def lfm2_moe_model(size: str = "8b-a1b", max_seq_len: int = 8192,
                   config: Optional[TransformerConfig] = None,
                   **overrides) -> ModelSpec:
    cfg = config or lfm2_moe_config(size, max_seq_len, **overrides)

    def loss_fn(params, batch, rng):
        _refuse_what_is_not_brought(cfg)
        return causal_lm_loss(cfg, params, batch, rng)

    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=loss_fn,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=lambda params, batch: logits_fn(
            cfg, params, transformer_forward(
                cfg, params,
                batch["input_ids"] if isinstance(batch, dict) else batch)[0]),
        flops_per_sample=flops_per_token(cfg, cfg.max_seq_len) * cfg.max_seq_len,
        buffers=(r"mlp/router_bias$",),
    )
    spec.config = cfg
    return spec
