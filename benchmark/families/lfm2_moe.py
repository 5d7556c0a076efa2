"""LFM2-MoE block: gated short-convolution layers and GQA layers (RMSNorm on q
and k heads, rotary) in the published ``layer_types`` order, a dense SwiGLU
feed-forward part in the first ``num_dense_layers`` layers and sigmoid-routed
experts with a selection bias in the others, head tied to the embedding.
Reads the keys of the published ``config.json`` plus the share the
configuration file states under ``deployment_share``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg["deployment_share"]
    assert cfg["use_expert_bias"] and not cfg["conv_bias"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["norm_eps"],
        "rope_theta": float(cfg["rope_theta"]),
        "layer_types": list(cfg["layer_types"]),
        "num_dense_layers": cfg["num_dense_layers"],
        "conv_taps": cfg["conv_L_cache"],
        "intermediate_size": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts_routed": share["num_experts_published"],
        "experts_held": cfg["num_experts"],
        "experts_first": share["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scaling_factor": float(cfg["routed_scaling_factor"]),
        "tie_word_embeddings": True,
        # leaves that are state and no parameter: the selection bias
        "buffers": ["router_bias"],
        # what roofline.param_count reads of a dense block (the log line of
        # train_steps; the cell's own count is moe_train_counts.py)
        "mlp": "swiglu", "position": "rope",
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/lfm2_moe.py``)."""
    from deepspeed_tpu.models.lfm2_moe import lfm2_moe_config, lfm2_moe_model

    d = describe(cfg)
    assert n_layers == len(d["layer_types"])
    return lfm2_moe_model(config=lfm2_moe_config(
        max_seq_len=max_seq_len, layer_types=d["layer_types"],
        hidden_size=d["hidden_size"], n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"], head_dim_override=d["head_dim"],
        vocab_size=d["vocab_size"], intermediate_size=d["expert_width"],
        dense_layers=d["num_dense_layers"],
        dense_ffn_size=d["intermediate_size"], conv_taps=d["conv_taps"],
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        moe_experts=d["experts_routed"], moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"],
        moe_routed_scale=d["routed_scaling_factor"],
        moe_held_first=d["experts_first"], moe_held_count=d["experts_held"],
        remat=bool(cfg.get("remat", False)), dtype=dtype))
