"""Solar-Open2 block: a period of one softmax GQA layer (no rotary, gated
output) and ``gqa_interval`` delta-rule linear-attention layers, an expert
layer with shared experts after every mixer.  Reads the keys of the published
``config.json`` plus the sizes the configuration file lists under ``assumed``
and the share it states under ``deployment_share``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    lin, share = cfg["linear_attn_config"], cfg["deployment_share"]
    assert cfg["n_shared_experts"] == 1 and cfg["first_k_dense_replace"] == 0
    assert not cfg["use_rope"] and cfg["use_gqa_gate"]
    assert lin["num_kv_heads"] is None and not cfg["kda_use_full_proj"]
    assert cfg["routed_scaling_factor"] == 1
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["rms_norm_eps"],
        "period": ["gqa"] + ["kda"] * cfg["gqa_interval"],
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "kda_conv": lin["short_conv_kernel_size"],
        "kda_rank": cfg["assumed_sizes"]["kda_low_rank"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_expert_width": cfg["assumed_sizes"]["shared_expert_width"],
        "experts_routed": share["n_routed_experts_published"],
        "experts_held": cfg["n_routed_experts"],
        "experts_first": share["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/solar_open2.py``)."""
    from deepspeed_tpu.models.solar_open2 import solar_open2_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return solar_open2_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"], head_dim_override=d["head_dim"],
        intermediate_size=d["expert_width"], max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="none",
        norm_eps=d["norm_eps"], tie_embeddings=d["tie_word_embeddings"],
        # the program names a layer type for its mixer; grouped-query is
        # n_kv_heads
        layer_period=tuple("attn" if k == "gqa" else k for k in d["period"]),
        attn_gate=True,
        kda_heads=d["kda_heads"], kda_head_dim=d["kda_head_dim"],
        kda_conv=d["kda_conv"], kda_rank=d["kda_rank"],
        moe_experts=d["experts_routed"], moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"],
        moe_held_first=d["experts_first"], moe_held_count=d["experts_held"],
        moe_shared_expert=d["shared_expert_width"], moe_shared_gate=False,
        dtype=dtype))
