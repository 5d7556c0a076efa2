"""Block-wise int8 quantization kernels.

TPU equivalent of the reference's quantization kernels
(``csrc/quantization/*`` — swizzled quant for ZeRO++ qwZ/qgZ): symmetric
per-block int8 quant/dequant used to compress gradients/weights before they
ride a collective (gradient_compression config).  The collective itself stays
an XLA op; compression halves/quarters the bytes on the wire.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...utils.platform import pallas_interpret


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # [rows, 128]
    absmax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)  # per-row scale
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    q_ref[...] = q
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = (q_ref[...].astype(jnp.float32) * s_ref[...]).astype(x_ref.dtype)


def quantize_int8(x: jnp.ndarray, block_rows: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Flat tensor -> (int8 codes [rows,128], fp32 scales [rows,1], orig_len)."""
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % 128
    if pad:
        flat = jnp.pad(flat, (0, pad))
    rows = flat.size // 128
    x2 = flat.reshape(rows, 128)
    br = min(rows, block_rows)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows, 128), jnp.int8),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)],
        interpret=pallas_interpret(),
    )(x2)
    return q, s, n


def dequantize_int8(q: jnp.ndarray, s: jnp.ndarray, orig_len: int,
                    dtype=jnp.float32, block_rows: int = 256) -> jnp.ndarray:
    rows = q.shape[0]
    br = min(rows, block_rows)
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, 128), lambda i: (i, 0)),
                  pl.BlockSpec((br, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 128), dtype),
        interpret=pallas_interpret(),
    )(q, s)
    return x.reshape(-1)[:orig_len]
