"""Xing4.0-29B-A4B: a residual of four streams mixed by manifold-constrained
hyper-connections (mHC, arXiv 2512.24880) round every mixer and every
feed-forward part — each sublayer reads ``H_pre X``, and ``H_res X + H_post^T
y`` goes back, ``H_res`` doubly stochastic by 20 Sinkhorn rounds — over
DeepSeek-V3's block: latent attention (MLA, rank 512 + 64 rotary, YaRN over
interleaved pairs) in every layer, a dense first layer, then 64 sigmoid-routed
experts (4 a token, a selection bias, weights renormalised and scaled by 2)
beside one ungated shared expert.

Served, not trained: the paged programs run it (``inference/v2``: the carry
of the layer loop is the ``hc_mult`` streams side by side, ``model_runner.
_stream_read`` / ``_stream_write`` the one pair every sublayer goes through;
the latent pages and the chunk and decode forms are Mistral-Small-4's); the
training entry names what is missing.  The multi-token-prediction layer
(``num_nextn_predict_layers``) is no part of it.  Every expert is held: the
benchmark's cut is depth alone (``benchmark/configs/xing4-29b-a4b-pp7-serve.
json``).
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, dense layers, heads, q_lora, kv_lora, qk_nope,
    #        qk_rope, v_dim, vocab, dense width, experts, top_k, expert width,
    #        streams, rope_factor, original_max)
    "tiny": (64, 4, 1, 4, 32, 32, 16, 8, 16, 256, 128, 8, 2, 32, 4, 8.0, 16),
    "29b": (3584, 40, 2, 32, 768, 512, 128, 64, 128, 131072, 9216, 64, 4,
            1024, 4, 64.0, 4096),
}


def xing4_runs(n_layers: int, dense_layers: int
               ) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """The dense prologue as a run of its own, then the expert layers."""
    if not 0 < dense_layers < n_layers:
        raise ValueError(f"{dense_layers} dense layers of {n_layers}: the "
                         "stack is a dense prologue and expert layers")
    return ((("mla",), dense_layers), (("mla",), n_layers - dense_layers))


def xing4_config(size: str = "29b", max_seq_len: int = 33808,
                 **overrides) -> TransformerConfig:
    (h, l, dense, nh, ql, kvl, dn, dr, dv, vocab, dense_width, experts,
     top_k, ew, streams, factor, original) = SIZES[size]
    l = overrides.pop("n_layers", l)
    dense = overrides.pop("dense_layers", dense)
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        head_dim_override=dn + dr, intermediate_size=ew,
        max_seq_len=max_seq_len, norm="rmsnorm", activation="swiglu",
        position="none", norm_eps=1e-6,
        layer_runs=xing4_runs(l, dense), dense_layers=dense,
        dense_ffn_size=dense_width,
        hc_mult=streams, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=30.0,
        q_lora_rank=ql, kv_lora_rank=kvl, qk_nope_head_dim=dn,
        qk_rope_head_dim=dr, v_head_dim=dv, rope_theta=10000.0,
        rope_factor=factor, rope_original_max=original, rope_beta_fast=32.0,
        rope_beta_slow=1.0, rope_mscale_all_dim=1.0, attn_scale_beta=0.0,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=True,
        moe_scoring="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.0, moe_held_first=0, moe_held_count=experts,
        moe_shared_expert=ew, moe_shared_gate=False)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "xing4 is served only: training it needs the latent form in the "
        "training forward (models/layer_types.py: 'mla' has no mix) and a "
        "residual of several streams there (models/transformer.py: "
        "transformer_forward carries one), and the smallest cut inside the "
        "floors (1 dense + 4 expert layers of 8 experts, an eighth of the "
        "vocabulary: 0.76 B parameters x 16 B = 12.1 GB) leaves no room for "
        "the activations of a residual four streams wide; it is no kernel "
        "that is missing")


def xing4_model(size: str = "29b", max_seq_len: int = 33808,
                config: Optional[TransformerConfig] = None,
                **overrides) -> ModelSpec:
    cfg = config or xing4_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
