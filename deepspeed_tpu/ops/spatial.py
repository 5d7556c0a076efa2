"""Spatial / diffusers inference ops (UNet & VAE path).

Reference parity: ``csrc/spatial/csrc/opt_bias_add.cu`` (fused NHWC
bias-add variants behind ``deepspeed.ops.transformer.inference.bias_add``)
and ``deepspeed/ops/transformer/inference/diffusers_attention.py``
(DeepSpeedDiffusersAttention).  The CUDA side exists because eager torch
launches one kernel per add; under jit XLA fuses these chains into a
single VPU loop, so the TPU-native implementation is the jnp expression —
the API surface and semantics (channels-last layout, fp32 accumulation
for the norm) are what's preserved.  The attention core routes through
the Pallas flash kernel on TPU (non-causal, no mask) — the same kernel
the reference reaches via its triton flash import.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ..utils.platform import on_tpu


# ---------------------------------------------------------------------------
# fused bias-add family (reference opt_bias_add.cu: add / add_add /
# bias_add_bias_add over [B, HW, C] half tensors)
# ---------------------------------------------------------------------------
def nhwc_bias_add(activation: jnp.ndarray, bias: jnp.ndarray) -> jnp.ndarray:
    """activation [B, HW, C] + bias [C]."""
    return activation + bias.astype(activation.dtype)


def nhwc_bias_add_add(activation: jnp.ndarray, bias: jnp.ndarray,
                      other: jnp.ndarray) -> jnp.ndarray:
    """(activation + bias) + other  (residual join)."""
    return activation + bias.astype(activation.dtype) + other


def nhwc_bias_add_bias_add(activation: jnp.ndarray, bias: jnp.ndarray,
                           other: jnp.ndarray,
                           other_bias: jnp.ndarray) -> jnp.ndarray:
    """(activation + bias) + (other + other_bias)."""
    return (activation + bias.astype(activation.dtype)
            + other + other_bias.astype(activation.dtype))


def group_norm(x: jnp.ndarray, num_groups: int, scale: jnp.ndarray,
               bias: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """GroupNorm over the channel dim of [B, HW, C] (UNet resnet blocks);
    fp32 statistics like every norm in this package."""
    B, HW, C = x.shape
    xf = x.astype(jnp.float32).reshape(B, HW, num_groups, C // num_groups)
    mu = jnp.mean(xf, axis=(1, 3), keepdims=True)
    var = jnp.var(xf, axis=(1, 3), keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = xf.reshape(B, HW, C) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# diffusers attention (reference DeepSpeedDiffusersAttention)
# ---------------------------------------------------------------------------
def diffusers_attention(x: jnp.ndarray, params: Dict[str, Any], n_heads: int,
                        context: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Self/cross attention over flattened spatial tokens.

    x: [B, HW, C]; context: [B, T, C_ctx] for cross-attention (None =>
    self).  params: {"wq" [C, C], "wk"/"wv" [C_ctx, C], "wo" [C, C],
    optional "bq"/"bk"/"bv"/"bo"}.  Non-causal; flash kernel on TPU.
    """
    B, HW, C = x.shape
    ctx = x if context is None else context
    D = C // n_heads

    def proj(inp, w, b):
        out = inp @ params[w]
        if params.get(b) is not None:
            out = out + params[b]
        return out

    q = proj(x, "wq", "bq").reshape(B, HW, n_heads, D)
    k = proj(ctx, "wk", "bk").reshape(B, ctx.shape[1], n_heads, D)
    v = proj(ctx, "wv", "bv").reshape(B, ctx.shape[1], n_heads, D)

    if on_tpu() and D in (64, 128) \
            and HW % 128 == 0 and ctx.shape[1] % 128 == 0:
        from .pallas.flash_attention import flash_attention

        attn = flash_attention(q, k, v, causal=False)
    else:
        scores = jnp.einsum("bqnd,bknd->bnqk", q, k).astype(jnp.float32)
        probs = jax.nn.softmax(scores / math.sqrt(D), axis=-1).astype(x.dtype)
        attn = jnp.einsum("bnqk,bknd->bqnd", probs, v)
    return proj(attn.reshape(B, HW, C), "wo", "bo")


def diffusers_transformer_block(x: jnp.ndarray, params: Dict[str, Any],
                                n_heads: int, context: jnp.ndarray,
                                norm_groups: int = 32) -> jnp.ndarray:
    """BasicTransformerBlock of the diffusers UNet (reference
    diffusers_transformer_block.py): self-attn -> cross-attn -> geglu FFN,
    each behind a layernorm with residual."""

    def ln(h, p):
        mu = jnp.mean(h.astype(jnp.float32), -1, keepdims=True)
        var = jnp.var(h.astype(jnp.float32), -1, keepdims=True)
        out = (h.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + 1e-5)
        return (out * p["scale"] + p["bias"]).astype(h.dtype)

    h = x + diffusers_attention(ln(x, params["norm1"]), params["attn1"], n_heads)
    h = h + diffusers_attention(ln(h, params["norm2"]), params["attn2"],
                                n_heads, context=context)
    # geglu FFN
    g = ln(h, params["norm3"]) @ params["ff"]["w_in"]
    val, gate = jnp.split(g, 2, axis=-1)
    return h + (val * jax.nn.gelu(gate)) @ params["ff"]["w_out"]
