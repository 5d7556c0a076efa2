"""Phi-4-mini-flash (SambaY) block: Mamba-1 and sliding-window
differential-attention layers, one full differential-attention layer, then
gated memory units and cross-attention layers that read that one layer's
pages; a dense SwiGLU after every mixer, LayerNorm with bias, a tied head.
Reads the keys of the published ``config.json`` plus the sizes the
configuration file lists under ``assumed_sizes``."""

from __future__ import annotations

from typing import Any, Dict


def runs(n_layers: int, mb_per_layer: int):
    """The stack as runs of periods ``[[types of a period], repeats]``: layer
    ``i`` even and ``<= L/2`` is Mamba, odd and ``< L/2`` window attention,
    ``L/2 + 1`` full attention, even beyond a gated memory unit, odd beyond
    cross-attention (the split at the middle is the configuration's
    ``assumed``)."""
    assert mb_per_layer == 2 and n_layers % 4 == 0 and n_layers >= 8
    return [[["mamba", "swa"], n_layers // 4], [["mamba", "dattn"], 1],
            [["gmu", "xattn"], n_layers // 4 - 1]]


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    assert cfg["hidden_act"] == "silu" and not cfg["mlp_bias"]
    assert not cfg["lm_head_bias"] and cfg["tie_word_embeddings"]
    nh, sizes = cfg["num_attention_heads"], cfg["assumed_sizes"]
    return {
        "hidden_size": cfg["hidden_size"],
        "intermediate_size": cfg["intermediate_size"],
        "num_attention_heads": nh,
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["hidden_size"] // nh,
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["layer_norm_eps"],
        "sliding_window": cfg["sliding_window"],
        "ssm_inner": sizes["d_inner"], "ssm_state": sizes["d_state"],
        "ssm_conv": sizes["d_conv"], "ssm_dt_rank": sizes["dt_rank"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "runs": runs(cfg["num_hidden_layers"], cfg["mb_per_layer"]),
        "tie_word_embeddings": True,
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/phi4_flash.py``)."""
    from deepspeed_tpu.models.phi4_flash import phi4_flash_model
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    return phi4_flash_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["num_attention_heads"],
        n_kv_heads=d["num_key_value_heads"],
        intermediate_size=d["intermediate_size"], max_seq_len=max_seq_len,
        norm="layernorm", activation="swiglu", position="none",
        norm_eps=d["norm_eps"], qkv_bias=True, tie_embeddings=True,
        layer_runs=tuple((tuple(period), n)
                         for period, n in runs(n_layers, cfg["mb_per_layer"])),
        sliding_window=d["sliding_window"], ssm_inner=d["ssm_inner"],
        ssm_state=d["ssm_state"], ssm_conv=d["ssm_conv"],
        ssm_dt_rank=d["ssm_dt_rank"], dtype=dtype))
