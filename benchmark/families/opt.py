"""OPT-style dense block: LayerNorm (pre-norm), ReLU MLP with biases, learned
positions, multi-head attention, head tied to the embedding.  Reads the keys
of the published ``config.json``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    nh = cfg["num_attention_heads"]
    return {
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["ffn_dim"],
        "num_attention_heads": nh,
        "num_key_value_heads": nh,
        "head_dim": cfg["hidden_size"] // nh,
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "mlp": cfg.get("activation_function", "relu"), "norm": "layernorm",
        "position": "learned", "bias": True,
        "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", True)),
        "norm_eps": 1e-5,
        "rope_theta": None,
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    from deepspeed_tpu.models.families import opt_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return opt_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"],
        intermediate_size=d["intermediate_size"], max_seq_len=max_seq_len,
        norm="layernorm", activation=d["mlp"], position="learned",
        use_bias=True, tie_embeddings=d["tie_word_embeddings"],
        norm_eps=d["norm_eps"], dtype=dtype))
