"""Xing4.0 block: a residual of ``hc_mult`` streams mixed by manifold-
constrained hyper-connections (Sinkhorn, ``hc_sinkhorn_iters`` rounds) round
every mixer and every feed-forward part; the mixer latent attention (MLA:
low-rank query and key/value projections, a head's query and key part rotary
and part not, YaRN tables over interleaved pairs), the feed-forward part dense
in the first ``first_k_dense_replace`` layers and afterwards an expert layer
with a sigmoid router, a selection bias and one ungated shared expert.  Reads
the keys of the published ``config.json``; every routed expert and the whole
vocabulary are held, and the multi-token-prediction layer
(``num_nextn_predict_layers``) is no part of what is served."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    rope = cfg["rope_scaling"]
    assert cfg["model_type"] == "xing4_0" and cfg["hc_mult"] > 1
    assert cfg["mhc_h_res_clamp_max"] == -cfg["mhc_h_res_clamp_min"] > 0
    assert cfg["n_shared_experts"] == 1 and cfg["moe_layer_freq"] == 1
    assert 0 < cfg["first_k_dense_replace"] < cfg["num_hidden_layers"]
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc" and cfg["ep_size"] == 1
    assert rope["type"] == "yarn" and rope["mscale"] == 1
    assert not cfg["attention_bias"] and cfg["hidden_act"] == "silu"
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
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
        "rope_theta": cfg["rope_theta"], "rope_factor": rope["factor"],
        "rope_original_max": rope["original_max_position_embeddings"],
        "rope_beta_fast": rope["beta_fast"],
        "rope_beta_slow": rope["beta_slow"],
        "rope_mscale_all_dim": rope["mscale_all_dim"],
        "dense_layers": cfg["first_k_dense_replace"],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "experts_routed": cfg["n_routed_experts"],
        "experts_held": cfg["n_routed_experts"],
        "experts_first": 0,
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
        "hc_mult": cfg["hc_mult"],
        "hc_sinkhorn_iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"],
        "hc_clamp": cfg["mhc_h_res_clamp_max"],
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/xing4.py``)."""
    from deepspeed_tpu.models.transformer import TransformerConfig
    from deepspeed_tpu.models.xing4 import xing4_model, xing4_runs

    d = describe(cfg)
    return xing4_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        head_dim_override=d["qk_nope_head_dim"] + d["qk_rope_head_dim"],
        intermediate_size=d["expert_width"], max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="none",
        norm_eps=d["norm_eps"], tie_embeddings=d["tie_word_embeddings"],
        layer_runs=xing4_runs(n_layers, d["dense_layers"]),
        dense_layers=d["dense_layers"], dense_ffn_size=d["dense_width"],
        hc_mult=d["hc_mult"], hc_sinkhorn_iters=d["hc_sinkhorn_iters"],
        hc_eps=float(d["hc_eps"]), hc_clamp=float(d["hc_clamp"]),
        q_lora_rank=d["q_lora_rank"], kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=d["qk_rope_head_dim"], v_head_dim=d["v_head_dim"],
        rope_theta=float(d["rope_theta"]), rope_factor=float(d["rope_factor"]),
        rope_original_max=d["rope_original_max"],
        rope_beta_fast=float(d["rope_beta_fast"]),
        rope_beta_slow=float(d["rope_beta_slow"]),
        rope_mscale_all_dim=float(d["rope_mscale_all_dim"]),
        attn_scale_beta=0.0,
        moe_experts=d["experts_routed"], moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"],
        moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=float(d["routed_scaling_factor"]),
        moe_held_first=0, moe_held_count=d["experts_held"],
        moe_shared_expert=d["expert_width"], moe_shared_gate=False,
        dtype=dtype))
