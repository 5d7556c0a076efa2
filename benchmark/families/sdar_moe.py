"""SDAR-MoE block: a Qwen3-MoE layer (grouped-query attention with an RMSNorm
on every q and k head, softmax-routed experts renormalised over the picks, no
shared expert) that generates by diffusion over blocks.  Reads the keys of the
published ``config.json`` plus what the configuration file states under
``assumed_values`` (the config has no key for the block length, the mask
token's id or the schedule)."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    assert cfg["model_type"] == "sdar_moe"
    assert cfg["decoder_sparse_step"] == 1 and not cfg["mlp_only_layers"]
    assert not cfg["attention_bias"] and cfg["hidden_act"] == "silu"
    assert cfg["rope_scaling"] is None and not cfg["use_sliding_window"]
    assert not cfg["tie_word_embeddings"]
    assumed = cfg["assumed_values"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "rope_theta": float(cfg["rope_theta"]),
        "norm_eps": cfg["rms_norm_eps"],
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "expert_width": cfg["moe_intermediate_size"],
        "num_experts": cfg["num_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "block_length": assumed["block_length"],
        "mask_token_id": assumed["mask_token_id"],
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/sdar_moe.py``)."""
    from deepspeed_tpu.models.sdar_moe import sdar_moe_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return sdar_moe_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"], head_dim_override=d["head_dim"],
        intermediate_size=d["expert_width"], max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        tie_embeddings=False, qk_norm=True, moe_experts=d["num_experts"],
        moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"], moe_scoring="softmax",
        moe_drop_tokens=False, moe_held_first=0,
        moe_held_count=d["num_experts"], block_length=d["block_length"],
        mask_token_id=d["mask_token_id"], dtype=dtype))
