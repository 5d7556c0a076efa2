"""Weight-quantized matmul (int8 / packed int4), Pallas TPU kernel.

Reference parity: ``deepspeed/inference/quantization/`` (weight-only int4/8
inference) and the fp6/int4 GEMMs in ``inference/v2/kernels/cutlass_ops`` —
the decode-path matmuls read quantized weights from HBM and dequantize
on-chip, so the weight HBM footprint AND bandwidth drop ~2x (int8) / ~4x
(int4) versus bf16.

Layout: weights are quantized symmetrically per ``group`` rows along the
contraction (K) dim: ``scale[g, n]`` covers rows ``[g*G, (g+1)*G)`` of
column n.  int4 codes store ``q + 8`` in the low/high nibbles of a uint8,
packed pairwise along K.  ``bits``/``group`` are STATIC (model-config
level) so the same compiled program serves every layer; codes/scales are
the only arrays.  The kernel dequantizes each K-group inside VMEM right
before its MXU contribution; the XLA fallback (CPU tests) dequantizes
whole and lets the compiler fuse.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import on_tpu, pallas_interpret


# ---------------------------------------------------------------------------
# packing (jnp only: vmappable over stacked layer dims)
# ---------------------------------------------------------------------------
def quantize_weight(w: jnp.ndarray, bits: int = 8,
                    group: int = 128) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K, N] float -> (codes, scale).  codes: int8 [Kp, N] (8-bit) or
    packed uint8 [Kp/2, N] (4-bit); scale: fp32 [Kp/group, N]."""
    assert w.ndim == 2, "weight-only quant expects [K, N] matrices"
    assert bits in (4, 8)
    K, N = w.shape
    pad = (-K) % group
    wf = jnp.pad(w.astype(jnp.float32), ((0, pad), (0, 0)))
    Kp = K + pad
    groups = wf.reshape(Kp // group, group, N)
    qmax = 127.0 if bits == 8 else 7.0
    scale = jnp.maximum(jnp.max(jnp.abs(groups), axis=1), 1e-12) / qmax
    q = jnp.clip(jnp.round(groups / scale[:, None, :]), -qmax, qmax)
    q = q.reshape(Kp, N)
    if bits == 8:
        return q.astype(jnp.int8), scale.astype(jnp.float32)
    off = (q + 8).astype(jnp.uint8)  # [0, 15]
    codes = (off[0::2] | (off[1::2] << 4)).astype(jnp.uint8)  # [Kp/2, N]
    return codes, scale.astype(jnp.float32)


def _unpack_int4(codes: jnp.ndarray) -> jnp.ndarray:
    """[Kp/2, N] uint8 -> [Kp, N] float32 in [-8, 7]."""
    lo = (codes & 0xF).astype(jnp.int32) - 8
    hi = (codes >> 4).astype(jnp.int32) - 8
    return jnp.stack([lo, hi], axis=1).reshape(
        codes.shape[0] * 2, codes.shape[1]).astype(jnp.float32)


def dequantize_weight(codes: jnp.ndarray, scale: jnp.ndarray, *, bits: int,
                      group: int, k: int, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Whole-matrix dequant (XLA fallback path).  ``k``: true K (un-padded)."""
    w = codes.astype(jnp.float32) if bits == 8 else _unpack_int4(codes)
    Kp, N = w.shape
    w = w.reshape(Kp // group, group, N) * scale[:, None, :]
    return w.reshape(Kp, N)[:k].astype(dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _wq_kernel(*refs, group, bits, groups_per_tile):
    """Grid (M tiles, N tiles, K tiles), K innermost; one K tile spans
    ``groups_per_tile`` quantization groups, each dequantized in VMEM
    right before its MXU contribution (static slices: Mosaic has no
    dynamic_slice on values).

    int4 packs rows (2i, 2i+1) into one byte, so the wrapper hands the
    kernel x's even and odd columns separately and each nibble plane
    meets its own half — no in-kernel row interleave."""
    n_x = 1 if bits == 8 else 2
    x_refs, (w_ref, s_ref, o_ref, acc_ref) = refs[:n_x], refs[n_x:]
    k = pl.program_id(2)
    rows = group if bits == 8 else group // 2  # code rows per group

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dt = x_refs[0].dtype
    acc = acc_ref[...]
    for g in range(groups_per_tile):
        sg = s_ref[g:g + 1, :]  # [1, bn]
        codes = w_ref[g * rows:(g + 1) * rows, :]
        if bits == 8:
            planes = (codes.astype(jnp.float32),)
        else:
            c = codes.astype(jnp.int32)
            planes = (((c & 0xF) - 8).astype(jnp.float32),
                      ((c >> 4) - 8).astype(jnp.float32))
        for x_ref, plane in zip(x_refs, planes):
            acc = acc + jnp.dot(x_ref[:, g * rows:(g + 1) * rows],
                                (plane * sg).astype(dt),
                                preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def wq_matmul(x: jnp.ndarray, codes: jnp.ndarray, scale: jnp.ndarray, *,
              bits: int, group: int = 128, block_m: int = 256,
              block_n: int = 512, impl: str = "auto") -> jnp.ndarray:
    """``x @ W`` with W stored quantized.  x: [..., K]; returns [..., N].

    int8/int4 codes are what crosses HBM; dequantization happens in VMEM
    per K-group right before the MXU contribution.  ``impl="auto"`` is
    the kernel on TPU — never the XLA dequant — and the XLA dequant on
    the CPU test tier, where interpreting would only slow the tests."""
    K = x.shape[-1]
    Kp = codes.shape[0] * (2 if bits == 4 else 1)
    N = codes.shape[1]

    lead = x.shape[:-1]
    xm = x.reshape(-1, K)
    M = xm.shape[0]

    if impl == "xla" or (impl == "auto" and not on_tpu()):
        w = dequantize_weight(codes, scale, bits=bits, group=group, k=K,
                              dtype=jnp.float32)
        out = (xm.astype(jnp.float32) @ w).astype(x.dtype)
        return out.reshape(*lead, N)

    if K != Kp:  # padded packing: extend x with zeros (pad weights are 0)
        xm = jnp.pad(xm, ((0, 0), (0, Kp - K)))

    bm = min(block_m, -(-M // 8) * 8)
    bn = min(block_n, N)
    pad_m = (-M) % bm
    pad_n = (-N) % bn
    if pad_m:
        xm = jnp.pad(xm, ((0, pad_m), (0, 0)))
    w, s = codes, scale
    if pad_n:
        w = jnp.pad(w, ((0, 0), (0, pad_n)))
        s = jnp.pad(s, ((0, 0), (0, pad_n)))
    n_groups = Kp // group
    # a K tile is 8 groups (the scale block's sublane tile) when that
    # divides K, else all of K
    gpt = 8 if n_groups % 8 == 0 else n_groups
    tk = gpt * group
    xs = (xm,) if bits == 8 else (xm[:, 0::2], xm[:, 1::2])
    xk = tk if bits == 8 else tk // 2  # x / code columns-rows per K tile

    out = pl.pallas_call(
        functools.partial(_wq_kernel, group=group, bits=bits,
                          groups_per_tile=gpt),
        grid=((M + pad_m) // bm, (N + pad_n) // bn, n_groups // gpt),
        in_specs=[pl.BlockSpec((bm, xk), lambda i, j, k: (i, k))] * len(xs)
        + [pl.BlockSpec((xk, bn), lambda i, j, k: (k, j)),
           pl.BlockSpec((gpt, bn), lambda i, j, k: (k, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((M + pad_m, N + pad_n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_wq_matmul",
    )(*xs, w, s)
    return out[:M, :N].reshape(*lead, N)
