"""Phi-4-mini-flash (SambaY): a self-decoder of Mamba-1 and sliding-window
differential-attention layers, one full differential-attention layer whose
keys and values are the only pages the model keeps, and a cross-decoder of
gated memory units (which read the last Mamba layer's scan output) and
cross-attention layers (which read that one layer's pages).  Every layer's
feed-forward part is a dense SwiGLU; LayerNorm with bias; a tied head; no
rotary or learned positions.

With ``L`` layers (``mb_per_layer`` 2, the split at the middle): layer ``i``
even and ``<= L/2`` is ``mamba``, odd and ``< L/2`` is ``swa``, ``L/2 + 1`` is
``dattn``, even beyond is ``gmu``, odd beyond is ``xattn`` — three runs of
periods, ``[mamba, swa] x L/4``, ``[mamba, dattn] x 1``, ``[gmu, xattn] x
(L/4 - 1)`` (``models/layer_types.py`` defines the types).

Served, not trained: the paged programs run it (``inference/v2``); prefill
runs the cross-decoder for a prompt's last token only, which is the
architecture's published property.  The training entry names what is missing.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..runtime.module import ModelSpec
from .transformer import (TransformerConfig, init_transformer_params,
                          transformer_partition_rules)

SIZES = {
    # name: (hidden, layers, heads, kv_heads, ffn, vocab, window, ssm_inner,
    #        ssm_state, ssm_conv, ssm_dt_rank)
    "tiny": (64, 12, 4, 2, 128, 256, 24, 128, 8, 4, 4),
    "mini": (2560, 32, 40, 20, 10240, 200064, 512, 5120, 16, 4, 160),
}


def phi4_flash_runs(n_layers: int) -> Tuple[Tuple[Tuple[str, ...], int], ...]:
    if n_layers % 4 or n_layers < 8:
        raise ValueError(f"n_layers {n_layers}: the stack is [mamba, swa] x "
                         "L/4, [mamba, dattn], [gmu, xattn] x (L/4 - 1)")
    return ((("mamba", "swa"), n_layers // 4), (("mamba", "dattn"), 1),
            (("gmu", "xattn"), n_layers // 4 - 1))


def phi4_flash_config(size: str = "mini", max_seq_len: int = 8192,
                      **overrides) -> TransformerConfig:
    (h, l, nh, kvh, ffn, vocab, window, di, ds, dc, rank) = SIZES[size]
    cfg = TransformerConfig(
        vocab_size=vocab, hidden_size=h, n_layers=l, n_heads=nh,
        n_kv_heads=kvh, intermediate_size=ffn, max_seq_len=max_seq_len,
        norm="layernorm", activation="swiglu", position="none",
        norm_eps=1e-5, qkv_bias=True, tie_embeddings=True,
        layer_runs=phi4_flash_runs(l), sliding_window=window, ssm_inner=di,
        ssm_state=ds, ssm_conv=dc, ssm_dt_rank=rank)
    for k, v in overrides.items():
        setattr(cfg, k, v)
    if "n_layers" in overrides and "layer_runs" not in overrides:
        cfg.layer_runs = phi4_flash_runs(cfg.n_layers)
    return cfg


def _no_training(*_a, **_k):
    raise NotImplementedError(
        "phi4_flash is served only: training it needs the backward of the "
        "selective scan (ops/pallas/ssm.py: dstpu_ssm_chunk), a window mask "
        "in the flash backward and the differential form in the training "
        "forward, none of which exists")


def phi4_flash_model(size: str = "mini", max_seq_len: int = 8192,
                     config: Optional[TransformerConfig] = None,
                     **overrides) -> ModelSpec:
    cfg = config or phi4_flash_config(size, max_seq_len, **overrides)
    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=_no_training,
        partition_rules=transformer_partition_rules(cfg),
        apply_fn=_no_training)
    spec.config = cfg
    return spec
