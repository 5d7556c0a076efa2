"""EvaByte-6.5B: a byte-level decoder (vocabulary 320) whose every layer is EVA
attention (Zheng et al., arXiv 2302.04542, in the deterministic learned form
EvaByte ships) — a query sees the ``window_size`` = 2,048 positions of its own
window exactly and every *closed* window through one pooled summary a
``chunk_size`` = 16 positions, all under one softmax — over a Llama block
(RMSNorm, rotary over the whole head, SwiGLU), with an untied head of
``num_pred_heads`` = 8 x 320: head ``i`` at position ``t`` predicts byte ``t +
1 + i``, logits in float32.

The type is ``layer_types.EVA``; what it caches is the open window's keys and
values and a summary row a closed chunk, both in K and V pages
(``inference/v2/ragged.EvaRows``), so a sequence of ``n`` bytes holds ``128
floor(n / 2048)`` visible summaries and at most 2,048 exact rows a layer.  The
paged programs serve it (``inference/v2/model_runner``: the chunk form over
``[visible summaries | the open window's rows | the chunk, causal]`` through
the flash kernel, the decode form over a composed page table through the paged
decode kernel; a row's window closes inside the decode program); the seven
further heads' picks ride beside the sampled byte and nothing verifies them
(``ROADMAP.md`` R12).  ``eva_mix`` is the plain whole-sequence form, for the
tests; the training entry names what is missing.
"""

from __future__ import annotations

from typing import Optional

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads, head_dim, ffn, vocab, heads of the
    #        prediction, window, chunk)
    "tiny": (64, 4, 4, 16, 128, 320, 8, 32, 4),
    "6.5b": (4096, 32, 32, 128, 11008, 320, 8, 2048, 16),
}


def evabyte_config(size: str = "6.5b", max_seq_len: int = 32768,
                   **overrides) -> TransformerConfig:
    h, l, nh, d, ffn, vocab, preds, window, chunk = SIZES[size]
    l = overrides.pop("n_layers", l)
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        head_dim_override=d, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="rmsnorm", activation="swiglu", position="rope",
        rope_theta=100000.0, norm_eps=1e-5, tie_embeddings=False,
        layer_period=("eva",), eva_window=window, eva_chunk=chunk,
        pred_heads=preds)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    check_eva(cfg)
    return cfg


def check_eva(cfg: TransformerConfig) -> None:
    W, C = cfg.eva_window, cfg.eva_chunk
    if C < 1 or W < C or W % C:
        raise ValueError(f"eva_window {W} is not a whole number of chunks of "
                         f"eva_chunk {C}")


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "evabyte is served only: training it at the lengths at which the "
        "mechanism matters needs the backward of a window-plus-summaries "
        "attention (ops/pallas/flash_attention.py: the forward takes the "
        "mask as a prefix of keys to skip; models/layer_types.py: eva_mix is "
        "the plain [S, S] form), a loss over eight prediction heads, and a "
        "cut inside the floors that leaves room for 32 k-byte activations "
        "(four layers are 0.82 B parameters x 16 B = 13.1 GB of 16)")


def evabyte_model(size: str = "6.5b", max_seq_len: int = 32768,
                  config: Optional[TransformerConfig] = None,
                  **overrides) -> ModelSpec:
    cfg = config or evabyte_config(size, max_seq_len, **overrides)
    check_eva(cfg)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
