"""EvaByte block: EVA attention in every layer — a query sees its own window of
``window_size`` positions exactly and every closed window through one pooled
summary a ``chunk_size`` positions, under one softmax — over a Llama block
(RMSNorm with a unit offset, rotary over the whole head, SwiGLU), bytes for a
vocabulary, and an untied head of ``num_pred_heads`` heads.  Reads the keys of
the published ``config.json``; the whole vocabulary and the whole head are
held, and the cut is depth."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    assert cfg["model_type"] == "evabyte" and cfg["attention_class"] == "eva"
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert not cfg["attention_bias"] and cfg["hidden_act"] == "silu"
    assert cfg["rope_scaling"] is None and not cfg["tie_word_embeddings"]
    assert cfg["norm_add_unit_offset"] and cfg["fp32_logits"]
    assert cfg["window_size"] % cfg["chunk_size"] == 0
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
        "intermediate_size": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "num_pred_heads": cfg["num_pred_heads"],
        "window_size": cfg["window_size"],
        "chunk_size": cfg["chunk_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_theta"],
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/evabyte.py``)."""
    from deepspeed_tpu.models.evabyte import evabyte_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return evabyte_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        head_dim_override=d["head_dim"],
        intermediate_size=d["intermediate_size"],
        max_seq_len=min(max_seq_len, d["max_position_embeddings"]),
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=float(d["rope_theta"]), norm_eps=d["norm_eps"],
        tie_embeddings=False, layer_period=("eva",),
        eva_window=d["window_size"], eva_chunk=d["chunk_size"],
        pred_heads=d["num_pred_heads"], dtype=dtype))
