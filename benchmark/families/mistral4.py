"""Mistral-Small-4 block: latent attention (MLA: low-rank query and key/value
projections, a head's query and key part rotary and part not, YaRN tables
over interleaved pairs) in every layer, an expert layer with a sigmoid router,
a selection bias and one ungated shared expert after every mixer.  Reads the
keys of the published ``config.json`` (the language model's: the vision tower
is no part of it) plus the share the configuration file states under
``deployment_share``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    rope, share = cfg["rope_parameters"], cfg["deployment_share"]
    assert cfg["n_shared_experts"] == 1 and cfg["first_k_dense_replace"] == 0
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert rope["rope_type"] == "yarn" and cfg["rope_interleave"]
    assert not cfg["attention_bias"] and not cfg["mlp_bias"]
    assert cfg["hidden_act"] == "silu"
    assert cfg["qk_head_dim"] == (cfg["qk_nope_head_dim"]
                                  + cfg["qk_rope_head_dim"])
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "q_lora_rank": cfg["q_lora_rank"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["rms_norm_eps"],
        "rope_theta": rope["rope_theta"], "rope_factor": rope["factor"],
        "rope_original_max": rope["original_max_position_embeddings"],
        "rope_beta_fast": rope["beta_fast"],
        "rope_beta_slow": rope["beta_slow"],
        "rope_mscale_all_dim": rope["mscale_all_dim"],
        "llama_4_scaling_beta": rope["llama_4_scaling_beta"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts_routed": share["n_routed_experts_published"],
        "experts_held": cfg["n_routed_experts"],
        "experts_first": share["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/mistral4.py``)."""
    from deepspeed_tpu.models.mistral4 import mistral4_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return mistral4_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        head_dim_override=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        intermediate_size=d["expert_width"], max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="none",
        norm_eps=d["norm_eps"], tie_embeddings=d["tie_word_embeddings"],
        layer_period=("mla",),
        q_lora_rank=d["q_lora_rank"], kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        rope_theta=float(d["rope_theta"]), rope_factor=float(d["rope_factor"]),
        rope_original_max=d["rope_original_max"],
        rope_beta_fast=float(d["rope_beta_fast"]),
        rope_beta_slow=float(d["rope_beta_slow"]),
        rope_mscale_all_dim=float(d["rope_mscale_all_dim"]),
        attn_scale_beta=float(d["llama_4_scaling_beta"]),
        moe_experts=d["experts_routed"], moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"],
        moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=float(d["routed_scaling_factor"]),
        moe_held_first=d["experts_first"], moe_held_count=d["experts_held"],
        moe_shared_expert=d["expert_width"], moe_shared_gate=False,
        dtype=dtype))
