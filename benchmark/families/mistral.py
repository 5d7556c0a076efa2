"""Mistral-style dense block: RMSNorm, SwiGLU, RoPE, grouped-query attention,
untied head.  Reads the keys of the published ``config.json``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    nh = cfg["num_attention_heads"]
    return {
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "num_attention_heads": nh,
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // nh,
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "mlp": "swiglu", "norm": "rmsnorm", "position": "rope",
        "bias": False,
        "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
        "norm_eps": cfg.get("rms_norm_eps", 1e-5),
        "rope_theta": cfg.get("rope_theta", 10000.0),
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/families.py``)."""
    from deepspeed_tpu.models.families import mistral_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return mistral_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"],
        intermediate_size=d["intermediate_size"], max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=d["rope_theta"], norm_eps=d["norm_eps"],
        tie_embeddings=d["tie_word_embeddings"], dtype=dtype))
