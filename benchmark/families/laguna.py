"""Laguna-S-2.1 block: full and window attention layers (``layer_types``)
whose QUERY head counts follow the type (``num_attention_heads_per_layer``)
over the same K/V heads, a gate a head (``gating: per-head``), a rotary table
and a rotated share of a head by type (``rope_parameters``: YaRN over half a
head on a full layer, the plain table over the whole head on a window layer),
a dense first layer (``mlp_only_layers``) and a softmax-routed expert layer
with a routed scale and a shared expert after every other mixer.  Reads the
keys of the published ``config.json`` — the per-layer lists whole, of which a
cut in depth runs the first ``num_hidden_layers`` — plus the share the
configuration file states under ``deployment_share``."""

from __future__ import annotations

from typing import Any, Dict


def describe(cfg: Dict[str, Any]) -> Dict[str, Any]:
    share = cfg["deployment_share"]
    # the per-layer lists stand as published; a cut in depth keeps a prefix
    n = cfg["num_hidden_layers"]
    kinds, heads, mlps, gates = (cfg[k][:n] for k in (
        "layer_types", "num_attention_heads_per_layer", "mlp_layer_types",
        "gating_types"))
    assert len(kinds) == len(heads) == len(mlps) == len(gates) == n
    assert set(kinds) <= {"full_attention", "sliding_attention"}
    assert cfg["mlp_only_layers"] == [0] and cfg["decoder_sparse_step"] == 1
    assert mlps == ["dense"] + ["sparse"] * (n - 1)
    assert cfg["gating"] == "per-head" and set(gates) == {"per_head"}
    assert not cfg["attention_bias"]
    assert not cfg["moe_router_logit_softcapping"]
    assert not cfg["moe_apply_router_weight_on_input"]
    full, window = (cfg["rope_parameters"][k] for k in (
        "full_attention", "sliding_attention"))
    assert full["rope_type"] == "yarn" and window["rope_type"] == "default"
    by_type = {k: {h for k2, h in zip(kinds, heads) if k2 == k}
               for k in set(kinds)}
    assert all(len(v) == 1 for v in by_type.values()), by_type
    heads_full = cfg["num_attention_heads"]
    assert by_type["full_attention"] == {heads_full}
    (heads_window,) = by_type.get("sliding_attention", {heads_full})
    d = cfg["head_dim"]
    return {
        "hidden_size": cfg["hidden_size"],
        "heads_full": heads_full, "heads_window": heads_window,
        "kv_heads": cfg["num_key_value_heads"], "head_dim": d,
        "sliding_window": cfg["sliding_window"],
        "rot_full": int(d * full["partial_rotary_factor"]) // 2 * 2,
        "rot_window": int(d * window["partial_rotary_factor"]) // 2 * 2,
        "partial_rotary_full": full["partial_rotary_factor"],
        "partial_rotary_window": window["partial_rotary_factor"],
        "rope_theta": full["rope_theta"],
        "swa_rope_theta": window["rope_theta"],
        "yarn": {k: full[k] for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "attention_factor")},
        "window_layers": [int(k == "sliding_attention") for k in kinds],
        "vocab_size": cfg["vocab_size"],
        "max_position_embeddings": cfg["max_position_embeddings"],
        "norm_eps": cfg["rms_norm_eps"],
        "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "shared_width": cfg["shared_expert_intermediate_size"],
        "experts_routed": share["num_experts_published"],
        "experts_held": cfg["num_experts"],
        "experts_first": share["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": bool(cfg["norm_topk_prob"]),
        "routed_scale": cfg["moe_routed_scaling_factor"],
        "tie_word_embeddings": bool(cfg["tie_word_embeddings"]),
    }


def build(cfg: Dict[str, Any], n_layers: int, max_seq_len: int, dtype):
    """The program's model for this configuration (``models/laguna.py``)."""
    from deepspeed_tpu.models.laguna import laguna_model, laguna_runs
    from deepspeed_tpu.models.transformer import TransformerConfig

    d = describe(cfg)
    runs = laguna_runs(n_layers)
    kinds = [k for period, n in runs for _ in range(n) for k in period]
    assert [int(k == "gqa_window") for k in kinds] == d["window_layers"]
    y = d["yarn"]
    return laguna_model(config=TransformerConfig(
        vocab_size=d["vocab_size"], hidden_size=d["hidden_size"],
        n_layers=n_layers, n_heads=d["heads_full"],
        swa_n_heads=d["heads_window"], n_kv_heads=d["kv_heads"],
        head_dim_override=d["head_dim"], intermediate_size=d["expert_width"],
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="none", norm_eps=d["norm_eps"],
        tie_embeddings=d["tie_word_embeddings"], layer_runs=runs,
        dense_layers=1, dense_ffn_size=d["dense_width"],
        sliding_window=d["sliding_window"], attn_head_gate=True,
        rope_theta=float(d["rope_theta"]),
        rotary_pct=float(d["partial_rotary_full"]),
        rope_factor=float(y["factor"]),
        rope_original_max=int(y["original_max_position_embeddings"]),
        rope_beta_fast=float(y["beta_fast"]),
        rope_beta_slow=float(y["beta_slow"]),
        rope_attention_factor=float(y["attention_factor"]),
        swa_rope_theta=float(d["swa_rope_theta"]),
        swa_rotary_pct=float(d["partial_rotary_window"]),
        moe_experts=d["experts_routed"], moe_top_k=d["num_experts_per_tok"],
        moe_norm_topk=d["norm_topk_prob"], moe_scoring="softmax",
        moe_routed_scale=float(d["routed_scale"]),
        moe_shared_expert=d["shared_width"], moe_shared_gate=False,
        moe_held_first=d["experts_first"], moe_held_count=d["experts_held"],
        dtype=dtype))
