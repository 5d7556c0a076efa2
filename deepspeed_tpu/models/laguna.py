"""Laguna-S-2.1: window (512) and full attention layers whose QUERY head
counts follow the layer's type — 72 on a window layer, 48 on a full one, over
8 K/V heads of 128 alike — every head's output through a learned gate (one
scalar a head, ``sigmoid(h w_g)``, before ``W_o``); a full layer rotates half
a head under YaRN (factor 128 over 8,192 positions, the attention factor on
the cos and sin of the rotated lanes), a window layer the whole head under the
plain table of another base; no sink.  Behind a dense first layer the
feed-forward part is 10 of 256 softmax-routed experts, their weights
renormalised and scaled by 2.5, plus one shared expert on every token.

Served, not trained: the paged programs run it (``inference/v2``: pages for
the full layers, rings in the slots for the window layers, what a layer
computes with and keeps following its type — ``layer_types.gqa_shape``); the
training entry names what is missing.  ``moe_held_first`` / ``moe_held_count``
and ``vocab_size`` make it one chip's share of an expert-parallel deployment
(``benchmark/configs/laguna-s-2.1-ep8-serve.json``).
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads full, heads window, head_dim, kv heads,
    #        window, vocab, dense width, experts, top_k, expert width,
    #        shared width, original positions)
    "tiny": (64, 9, 12, 18, 16, 2, 16, 256, 128, 8, 2, 32, 32, 32),
    "118b": (3072, 48, 48, 72, 128, 8, 512, 100352, 12288, 256, 10, 1024,
             1024, 8192),
}

PERIOD = ("gqa_window",) * 3 + ("gqa_full",)


def laguna_runs(n_layers: int) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    """The published ``layer_types`` as runs: the dense layer 0 (full
    attention) alone, then whole periods of three window layers and a full
    one — and, at the published depth of 48 alone, the three window layers
    the list ends with.  A cut keeps layer 0 and whole periods; anything else
    is refused."""
    periods, rest = divmod(n_layers - 1, len(PERIOD))
    tail = ((PERIOD[:rest], 1),) if n_layers == SIZES["118b"][1] else ()
    if n_layers < 1 or (rest and not tail):
        raise ValueError(f"{n_layers} layers are not the dense first layer "
                         f"and whole periods of {len(PERIOD)}")
    return ((("gqa_full",), 1), *(((PERIOD, periods),) if periods else ()),
            *tail)


def laguna_config(size: str = "118b", max_seq_len: int = 32768,
                  **overrides) -> TransformerConfig:
    (h, l, nh, nh_win, d, kv, window, vocab, dense, experts, top_k, ew,
     shared, original) = SIZES[size]
    l = overrides.pop("n_layers", l)
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        swa_n_heads=nh_win, n_kv_heads=kv, head_dim_override=d,
        intermediate_size=ew, max_seq_len=max_seq_len, norm="rmsnorm",
        activation="swiglu", position="none", norm_eps=1e-6,
        layer_runs=laguna_runs(l), dense_layers=1, dense_ffn_size=dense,
        sliding_window=window, attn_head_gate=True,
        # full layers: half a head under YaRN; window layers: the whole head
        # under the plain table of their own base
        rope_theta=5e5, rotary_pct=0.5, rope_factor=128.0,
        rope_original_max=original, rope_beta_fast=32.0, rope_beta_slow=1.0,
        rope_attention_factor=1.4852030263919618,
        swa_rope_theta=1e4, swa_rotary_pct=1.0,
        moe_experts=experts, moe_top_k=top_k, moe_norm_topk=True,
        moe_scoring="softmax", moe_routed_scale=2.5,
        moe_shared_expert=shared, moe_shared_gate=False)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "laguna is served only: training it needs a window in the flash "
        "backward (ops/pallas/flash_attention.py: the forward has it, "
        "models/layer_types.py: 'gqa_full' and 'gqa_window' have no mix, "
        "and the training forward has neither the head gate nor query heads "
        "and rotary tables by type); at 16 B a parameter the smallest "
        "cut inside the floors (the dense layer, one period of 8 experts a "
        "layer, an eighth of the vocabulary: 0.81 B parameters) is 13.0 GB "
        "before a single activation")


def laguna_model(size: str = "118b", max_seq_len: int = 32768,
                 config: Optional[TransformerConfig] = None,
                 **overrides) -> ModelSpec:
    cfg = config or laguna_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
