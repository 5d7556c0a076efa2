"""Additional model families on the shared transformer core.

Parity target: the reference's per-architecture support surface —
inference v2 model implementations (``inference/v2/model_implementations/
{mistral,qwen,phi,opt,falcon}``) and AutoTP containers
(``module_inject/containers/``).  Each family is a TransformerConfig
recipe; the compute path (training forward, KV-cache decode, paged
prefill/decode, TP/SP/ZeRO shardings) is shared with llama/gpt2.

Family-specific structure carried by the config:
  mistral — llama-shape with GQA (the reference's sliding-window attention
            is full causal attention here: its window never binds at the
            contexts served; a window that binds is the ``swa`` layer type
            of ``models/layer_types.py``, with a ring for a cache, which
            ``models/phi4_flash.py`` serves)
  qwen2   — llama-shape + biases on q/k/v only (``qkv_bias``)
  phi     — partial rotary (``rotary_pct``), parallel attn+MLP block,
            layernorm + gelu + biases
  opt     — learned positions, relu MLP, layernorm, biases
  falcon  — multi-query attention (kv_heads=1), parallel block, rope
  bloom   — ALiBi attention bias, word_embeddings_layernorm, tied head
  gpt-neox— partial rotary, parallel residual with separate norms,
            untied embed_out

Families with layer types of their own live beside this file, each served
through ``inference/v2`` and refused for training by name: ``solar_open2.py``
(GQA + delta-rule layers over an expert share), ``phi4_flash.py`` (Mamba,
window and cross-attention layers, gated memory units), ``mistral4.py``
(latent attention over a paged latent leaf, a sigmoid-routed expert share),
``mimo_v2.py`` (full layers with pages and window layers with rings, of
different K/V head counts, keys wider than values, behind a dense first layer),
``laguna.py`` (the same two types with query heads, rotary table and rotated
share by type, a gate a head, a shared expert), ``xing4.py`` (a residual of
four streams mixed by hyper-connections round latent attention and a
sigmoid-routed expert layer held whole, behind a dense first layer),
``evabyte.py`` (EVA attention: an exact window beside pooled summaries of the
closed ones, a cache that grows a row a chunk, eight prediction heads of 320);
``lfm2_moe.py`` (short-convolution + GQA layers over an expert share) is
trained and not served.
"""

from __future__ import annotations

from typing import Optional

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, causal_lm_loss, flops_per_token,
                          init_transformer_params, logits_fn,
                          transformer_forward, transformer_partition_rules)


def _spec(cfg: TransformerConfig) -> ModelSpec:
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=lambda params, batch, rng: causal_lm_loss(cfg, params, batch, rng),
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=lambda params, batch: logits_fn(
            cfg, params, transformer_forward(
                cfg, params,
                batch["input_ids"] if isinstance(batch, dict) else batch)[0]),
        flops_per_sample=flops_per_token(cfg, cfg.max_seq_len) * cfg.max_seq_len,
    )
    spec.config = cfg
    return spec


def _apply(cfg: TransformerConfig, overrides) -> TransformerConfig:
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return cfg


# --------------------------------------------------------------- mistral
MISTRAL_SIZES = {
    "tiny": (64, 2, 4, 2, 128, 256),
    "7b": (4096, 32, 32, 8, 14336, 32000),
}


def mistral_config(size: str = "7b", max_seq_len: int = 4096,
                   **overrides) -> TransformerConfig:
    h, l, nh, kvh, ffn, vocab = MISTRAL_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=10000.0), overrides)


def mistral_model(size: str = "7b", max_seq_len: int = 4096,
                  config: Optional[TransformerConfig] = None,
                  **overrides) -> ModelSpec:
    return _spec(config or mistral_config(size, max_seq_len, **overrides))


# ----------------------------------------------------------------- qwen
QWEN_SIZES = {
    "tiny": (64, 2, 4, 4, 128, 256),
    "0.5b": (896, 24, 14, 2, 4864, 151936),
    "7b": (3584, 28, 28, 4, 18944, 152064),
}


def qwen_config(size: str = "7b", max_seq_len: int = 4096,
                **overrides) -> TransformerConfig:
    h, l, nh, kvh, ffn, vocab = QWEN_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=1e6, qkv_bias=True), overrides)


def qwen_model(size: str = "7b", max_seq_len: int = 4096,
               config: Optional[TransformerConfig] = None,
               **overrides) -> ModelSpec:
    return _spec(config or qwen_config(size, max_seq_len, **overrides))


# ------------------------------------------------------------------ phi
PHI_SIZES = {
    "tiny": (64, 2, 4, 4, 128, 256),
    "1.5": (2048, 24, 32, 32, 8192, 51200),
    "2": (2560, 32, 32, 32, 10240, 51200),
}


def phi_config(size: str = "2", max_seq_len: int = 2048,
               **overrides) -> TransformerConfig:
    h, l, nh, kvh, ffn, vocab = PHI_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="layernorm", activation="gelu", position="rope",
        rotary_pct=0.4, parallel_block=True, use_bias=True), overrides)


def phi_model(size: str = "2", max_seq_len: int = 2048,
              config: Optional[TransformerConfig] = None,
              **overrides) -> ModelSpec:
    return _spec(config or phi_config(size, max_seq_len, **overrides))


# ------------------------------------------------------------------ opt
OPT_SIZES = {
    "tiny": (64, 2, 4, 4, 128, 256),
    "125m": (768, 12, 12, 12, 3072, 50272),
    "1.3b": (2048, 24, 32, 32, 8192, 50272),
    "6.7b": (4096, 32, 32, 32, 16384, 50272),
}


def opt_config(size: str = "1.3b", max_seq_len: int = 2048,
               **overrides) -> TransformerConfig:
    h, l, nh, kvh, ffn, vocab = OPT_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="layernorm", activation="relu", position="learned",
        use_bias=True, tie_embeddings=True), overrides)


def opt_model(size: str = "1.3b", max_seq_len: int = 2048,
              config: Optional[TransformerConfig] = None,
              **overrides) -> ModelSpec:
    return _spec(config or opt_config(size, max_seq_len, **overrides))


# --------------------------------------------------------------- falcon
FALCON_SIZES = {
    "tiny": (64, 2, 4, 1, 128, 256),
    "7b": (4544, 32, 71, 1, 18176, 65024),
}


def falcon_config(size: str = "7b", max_seq_len: int = 2048,
                  **overrides) -> TransformerConfig:
    h, l, nh, kvh, ffn, vocab = FALCON_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="layernorm", activation="gelu_exact", position="rope",
        parallel_block=True), overrides)


def falcon_model(size: str = "7b", max_seq_len: int = 2048,
                 config: Optional[TransformerConfig] = None,
                 **overrides) -> ModelSpec:
    return _spec(config or falcon_config(size, max_seq_len, **overrides))


# --------------------------------------------------------------- bloom
# reference parity: module_inject/containers/bloom.py + the BLOOM policy —
# ALiBi position bias, MHA, layernorm + gelu + biases everywhere, bloom's
# word_embeddings_layernorm, tied head
BLOOM_SIZES = {
    # name: (hidden, layers, heads, vocab)
    "tiny": (64, 2, 4, 256),
    "560m": (1024, 24, 16, 250880),
    "7b1": (4096, 30, 32, 250880),
    "176b": (14336, 70, 112, 250880),
}


def bloom_config(size: str = "560m", max_seq_len: int = 2048,
                 **overrides) -> TransformerConfig:
    h, l, nh, vocab = BLOOM_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        intermediate_size=4 * h, max_seq_len=max_seq_len,
        norm="layernorm", activation="gelu", position="alibi",
        use_bias=True, embed_norm=True, tie_embeddings=True,
        norm_eps=1e-5), overrides)


def bloom_model(size: str = "560m", max_seq_len: int = 2048,
                config: Optional[TransformerConfig] = None,
                **overrides) -> ModelSpec:
    return _spec(config or bloom_config(size, max_seq_len, **overrides))


# --------------------------------------------------------------- gpt-neox
# reference parity: module_inject/containers/gptneox.py — partial rotary
# (rotary_pct), parallel attention+MLP residual with SEPARATE input/
# post-attention norms, layernorm + gelu + biases, untied embed_out
NEOX_SIZES = {
    # name: (hidden, layers, heads, ffn, vocab)
    "tiny": (64, 2, 4, 128, 256),
    "20b": (6144, 44, 64, 24576, 50432),
}


def gpt_neox_config(size: str = "20b", max_seq_len: int = 2048,
                    **overrides) -> TransformerConfig:
    h, l, nh, ffn, vocab = NEOX_SIZES[size]
    return _apply(TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="layernorm", activation="gelu_exact", position="rope",
        rotary_pct=0.25, use_bias=True, parallel_block=True,
        parallel_norms=2, norm_eps=1e-5), overrides)


def gpt_neox_model(size: str = "20b", max_seq_len: int = 2048,
                   config: Optional[TransformerConfig] = None,
                   **overrides) -> ModelSpec:
    return _spec(config or gpt_neox_config(size, max_seq_len, **overrides))
