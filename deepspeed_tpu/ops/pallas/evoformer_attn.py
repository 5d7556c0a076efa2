"""Evoformer attention — fused Pallas TPU kernels.

The TPU-native replacement for the reference's CUTLASS evoformer kernels
(``csrc/deepspeed4science/evoformer_attn/kernel_forward.h`` /
``kernel_backward.h``, ~14.9k LoC): AlphaFold-style attention over
[B, S, N, H, D] (batch, n_seq rows, n_res, heads, head_dim) with up to two
additive biases broadcast into the scores —

  bias1: [B, S, 1, 1, K]  row-wise mask bias   (broadcast over heads + q)
  bias2: [B, 1, H, Q, K]  pair-representation  (broadcast over seq rows)

Forward is a blocked online-softmax (never materializes [.., Q, K] in HBM);
backward recomputes probabilities from the saved log-sum-exp and produces
dq/dk/dv *and both bias gradients* — the part autodiff cannot do without
materializing the full score tensor (dbias2 alone is a sum over the S axis
of a [B,S,H,Q,K] intermediate that can reach tens of GB at AlphaFold
shapes).

Bias-gradient accumulation exploits the TPU Pallas sequential grid:
  * dbias1[b,s]  accumulates over (h, iq)  — grid (B, S, H, nq), the
    (h, iq) iterations for a fixed (b, s) are consecutive, so the output
    block is revisited consecutively and stays resident in VMEM.
  * dbias2[b,h,jk] accumulates over s      — grid (B, H, nk, S), s is the
    fastest axis for the same reason.
Falls back to interpreter mode off-TPU so CPU CI runs the same code.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...utils.platform import pallas_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward: grid (B, S, H, nq)
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, block_k, seq_k,
                has_b1, has_b2):
    idx = 0
    b1_ref = rest[idx] if has_b1 else None
    idx += 1 if has_b1 else 0
    b2_ref = rest[idx] if has_b2 else None
    idx += 1 if has_b2 else 0
    o_ref, lse_ref = rest[idx], rest[idx + 1]

    q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale  # [bq, D]
    bq, d = q.shape
    nk = pl.cdiv(seq_k, block_k)

    def body(j, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, 0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = q @ k_blk.T  # [bq, bk]
        if has_b1:
            s = s + b1_ref[0, 0, pl.ds(j * block_k, block_k)].astype(jnp.float32)[None, :]
        if has_b2:
            s = s + b2_ref[0, 0, :, pl.ds(j * block_k, block_k)].astype(jnp.float32)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        s = jnp.where(cols < seq_k, s, NEG_INF)  # padded tail of K
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + p @ v_blk
        return acc, m_new, l_new

    acc = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk, body, (acc, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0, 0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0, 0] = (m + jnp.log(l)).astype(jnp.float32)


def _fwd(q5, k5, v5, b1, b2, sm_scale, block_q, block_k):
    """q5/k5/v5: [B, S, H, N, D] (already transposed).  b1: [B,S,K] or None;
    b2: [B,H,Q,K] or None.  Returns out [B,S,H,Q,D], lse [B,S,H,Q,1]."""
    B, S, H, Q, D = q5.shape
    K = k5.shape[3]
    bq = min(block_q, Q)
    bk = min(block_k, K)
    pad_q = (-Q) % bq
    pad_k = (-K) % bk
    if pad_q:
        q5 = jnp.pad(q5, ((0, 0),) * 3 + ((0, pad_q), (0, 0)))
    if pad_k:
        k5 = jnp.pad(k5, ((0, 0),) * 3 + ((0, pad_k), (0, 0)))
        v5 = jnp.pad(v5, ((0, 0),) * 3 + ((0, pad_k), (0, 0)))
        if b1 is not None:
            b1 = jnp.pad(b1, ((0, 0), (0, 0), (0, pad_k)))
        if b2 is not None:
            b2 = jnp.pad(b2, ((0, 0), (0, 0), (0, 0), (0, pad_k)))
    if pad_q and b2 is not None:
        b2 = jnp.pad(b2, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    Qp, Kp = Q + pad_q, K + pad_k

    grid = (B, S, H, Qp // bq)
    in_specs = [
        pl.BlockSpec((1, 1, 1, bq, D), lambda b, s, h, i: (b, s, h, i, 0)),
        pl.BlockSpec((1, 1, 1, Kp, D), lambda b, s, h, i: (b, s, h, 0, 0)),
        pl.BlockSpec((1, 1, 1, Kp, D), lambda b, s, h, i: (b, s, h, 0, 0)),
    ]
    args = [q5, k5, v5]
    if b1 is not None:
        in_specs.append(pl.BlockSpec((1, 1, Kp), lambda b, s, h, i: (b, s, 0)))
        args.append(b1)
    if b2 is not None:
        in_specs.append(pl.BlockSpec((1, 1, bq, Kp), lambda b, s, h, i: (b, h, i, 0)))
        args.append(b2)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, block_k=bk,
                          seq_k=K, has_b1=b1 is not None, has_b2=b2 is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, 1, bq, D), lambda b, s, h, i: (b, s, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq, 1), lambda b, s, h, i: (b, s, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, Qp, D), q5.dtype),
            jax.ShapeDtypeStruct((B, S, H, Qp, 1), jnp.float32),
        ],
        interpret=pallas_interpret(),
    )(*args)
    return out[:, :, :, :Q], lse[:, :, :, :Q]


# ---------------------------------------------------------------------------
# backward A: dq (+ dbias1) — grid (B, S, H, nq)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   sm_scale, block_k, seq_k, has_b1, has_b2, want_db1):
    idx = 0
    b1_ref = rest[idx] if has_b1 else None
    idx += 1 if has_b1 else 0
    b2_ref = rest[idx] if has_b2 else None
    idx += 1 if has_b2 else 0
    dq_ref = rest[idx]
    db1_ref = rest[idx + 1] if want_db1 else None

    q = q_ref[0, 0, 0].astype(jnp.float32)
    do = do_ref[0, 0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0]
    delta = delta_ref[0, 0, 0]
    bq, d = q.shape
    nk = pl.cdiv(seq_k, block_k)

    if want_db1:
        # dbias1[b, s] accumulates over this grid's (h, iq): zero it on the
        # first visit of each (b, s)
        @pl.when((pl.program_id(2) == 0) & (pl.program_id(3) == 0))
        def _():
            db1_ref[0, 0] = jnp.zeros_like(db1_ref[0, 0])

    def body(j, dq):
        k_blk = k_ref[0, 0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k_blk.T) * sm_scale
        if has_b1:
            s = s + b1_ref[0, 0, pl.ds(j * block_k, block_k)].astype(jnp.float32)[None, :]
        if has_b2:
            s = s + b2_ref[0, 0, :, pl.ds(j * block_k, block_k)].astype(jnp.float32)
        cols = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        valid = cols < seq_k
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)  # [bq, bk]
        dp = do @ v_blk.T
        ds = p * (dp - delta)  # dscore (bias grad units; dq needs *scale)
        if want_db1:
            cur = db1_ref[0, 0, pl.ds(j * block_k, block_k)]
            db1_ref[0, 0, pl.ds(j * block_k, block_k)] = \
                cur + jnp.sum(ds, axis=0).astype(jnp.float32)
        return dq + (ds * sm_scale) @ k_blk

    dq = jax.lax.fori_loop(0, nk, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0, 0] = dq.astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward B: dk/dv (+ dbias2) — grid (B, H, nk, S); s fastest for the
# consecutive-revisit accumulation of dbias2[b, h, jk]
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    sm_scale, block_q, seq_q, seq_k, has_b1, has_b2,
                    want_db2):
    idx = 0
    b1_ref = rest[idx] if has_b1 else None
    idx += 1 if has_b1 else 0
    b2_ref = rest[idx] if has_b2 else None
    idx += 1 if has_b2 else 0
    dk_ref, dv_ref = rest[idx], rest[idx + 1]
    db2_ref = rest[idx + 2] if want_db2 else None

    k_blk = k_ref[0, 0, 0].astype(jnp.float32)  # [bk, D]
    v_blk = v_ref[0, 0, 0].astype(jnp.float32)
    bk, d = k_blk.shape
    jk = pl.program_id(2)
    k_start = jk * bk
    nq = pl.cdiv(seq_q, block_q)

    if want_db2:
        @pl.when(pl.program_id(3) == 0)  # first s for this (b, h, jk)
        def _():
            db2_ref[0, 0] = jnp.zeros_like(db2_ref[0, 0])

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, 0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, 0, 0, pl.ds(i * block_q, block_q), :]
        s = (q @ k_blk.T) * sm_scale  # [bq, bk]
        if has_b1:
            s = s + b1_ref[0, 0, pl.ds(k_start, bk)].astype(jnp.float32)[None, :]
        if has_b2:
            s = s + b2_ref[0, 0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        rows = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
        valid = (rows < seq_q) & (cols < seq_k)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dv = dv + p.T @ do
        dp = do @ v_blk.T
        ds = p * (dp - delta)  # dscore
        if want_db2:
            cur = db2_ref[0, 0, pl.ds(i * block_q, block_q), :]
            db2_ref[0, 0, pl.ds(i * block_q, block_q), :] = \
                cur + ds.astype(jnp.float32)
        dk = dk + (ds * sm_scale).T @ q
        return dk, dv

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, nq, body, (dk0, dv0))
    dk_ref[0, 0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0, 0] = dv.astype(dv_ref.dtype)


def _bwd(sm_scale, block_q, block_k, has_b1, has_b2, res, do5):
    q5, k5, v5, b1, b2, out, lse = res
    B, S, H, Q, D = q5.shape
    K = k5.shape[3]
    bq = min(block_q, Q)
    bk = min(block_k, K)
    pad_q = (-Q) % bq
    pad_k = (-K) % bk
    Qp, Kp = Q + pad_q, K + pad_k

    delta = jnp.sum(out.astype(jnp.float32) * do5.astype(jnp.float32), -1,
                    keepdims=True)  # [B,S,H,Q,1]

    def padq(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, pad_q), (0, 0))) if pad_q else x

    def padk(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, pad_k), (0, 0))) if pad_k else x

    q5p, do5p = padq(q5), padq(do5)
    lse_p, delta_p = padq(lse), padq(delta)
    k5p, v5p = padk(k5), padk(v5)
    b1p = (jnp.pad(b1, ((0, 0), (0, 0), (0, pad_k))) if pad_k else b1) \
        if b1 is not None else None
    b2p = b2
    if b2 is not None:
        if pad_q:
            b2p = jnp.pad(b2p, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        if pad_k:
            b2p = jnp.pad(b2p, ((0, 0), (0, 0), (0, 0), (0, pad_k)))

    # ---- pass A: dq + dbias1, grid (B, S, H, nq)
    bias_specs, bias_args = [], []
    if b1p is not None:
        bias_specs.append(pl.BlockSpec((1, 1, Kp), lambda b, s, h, i: (b, s, 0)))
        bias_args.append(b1p)
    if b2p is not None:
        bias_specs.append(pl.BlockSpec((1, 1, bq, Kp), lambda b, s, h, i: (b, h, i, 0)))
        bias_args.append(b2p)
    out_specs = [pl.BlockSpec((1, 1, 1, bq, D), lambda b, s, h, i: (b, s, h, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, S, H, Qp, D), q5.dtype)]
    if has_b1:
        # accumulated over (h, iq): block index pins to (b, s)
        out_specs.append(pl.BlockSpec((1, 1, Kp), lambda b, s, h, i: (b, s, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, S, Kp), jnp.float32))
    res_a = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, block_k=bk,
                          seq_k=K, has_b1=has_b1, has_b2=has_b2,
                          want_db1=has_b1),
        grid=(B, S, H, Qp // bq),
        in_specs=[
            pl.BlockSpec((1, 1, 1, bq, D), lambda b, s, h, i: (b, s, h, i, 0)),
            pl.BlockSpec((1, 1, 1, Kp, D), lambda b, s, h, i: (b, s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Kp, D), lambda b, s, h, i: (b, s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bq, D), lambda b, s, h, i: (b, s, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq, 1), lambda b, s, h, i: (b, s, h, i, 0)),
            pl.BlockSpec((1, 1, 1, bq, 1), lambda b, s, h, i: (b, s, h, i, 0)),
        ] + bias_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=pallas_interpret(),
    )(q5p, k5p, v5p, do5p, lse_p, delta_p, *bias_args)
    # out_shape is a list, so pallas_call returns a list even with one entry
    dq = res_a[0][:, :, :, :Q]
    db1 = res_a[1][:, :, :K] if has_b1 else None

    # ---- pass B: dk/dv + dbias2, grid (B, H, nk, S) — s fastest
    bias_specs_b, bias_args_b = [], []
    if b1p is not None:
        bias_specs_b.append(pl.BlockSpec((1, 1, Kp), lambda b, h, j, s: (b, s, 0)))
        bias_args_b.append(b1p)
    if b2p is not None:
        bias_specs_b.append(
            pl.BlockSpec((1, 1, Qp, bk), lambda b, h, j, s: (b, h, 0, j)))
        bias_args_b.append(b2p)
    out_specs_b = [
        pl.BlockSpec((1, 1, 1, bk, D), lambda b, h, j, s: (b, s, h, j, 0)),
        pl.BlockSpec((1, 1, 1, bk, D), lambda b, h, j, s: (b, s, h, j, 0)),
    ]
    out_shape_b = [
        jax.ShapeDtypeStruct((B, S, H, Kp, D), k5.dtype),
        jax.ShapeDtypeStruct((B, S, H, Kp, D), v5.dtype),
    ]
    if has_b2:
        # accumulated over s: block index pins to (b, h, jk)
        out_specs_b.append(pl.BlockSpec((1, 1, Qp, bk), lambda b, h, j, s: (b, h, 0, j)))
        out_shape_b.append(jax.ShapeDtypeStruct((B, H, Qp, Kp), jnp.float32))
    res_b = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, block_q=bq,
                          seq_q=Q, seq_k=K, has_b1=has_b1, has_b2=has_b2,
                          want_db2=has_b2),
        grid=(B, H, Kp // bk, S),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Qp, D), lambda b, h, j, s: (b, s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, bk, D), lambda b, h, j, s: (b, s, h, j, 0)),
            pl.BlockSpec((1, 1, 1, bk, D), lambda b, h, j, s: (b, s, h, j, 0)),
            pl.BlockSpec((1, 1, 1, Qp, D), lambda b, h, j, s: (b, s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Qp, 1), lambda b, h, j, s: (b, s, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Qp, 1), lambda b, h, j, s: (b, s, h, 0, 0)),
        ] + bias_specs_b,
        out_specs=out_specs_b,
        out_shape=out_shape_b,
        interpret=pallas_interpret(),
    )(q5p, k5p, v5p, do5p, lse_p, delta_p, *bias_args_b)
    dk = res_b[0][:, :, :, :K]
    dv = res_b[1][:, :, :, :K]
    db2 = res_b[2][:, :, :Q, :K] if has_b2 else None
    return dq, dk, dv, db1, db2


# ---------------------------------------------------------------------------
# custom VJP over [B,S,H,N,D]-transposed operands
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _evo_core(q5, k5, v5, b1, b2, sm_scale, block_q, block_k):
    out, _ = _fwd(q5, k5, v5, b1, b2, sm_scale, block_q, block_k)
    return out


def _evo_fwd_rule(q5, k5, v5, b1, b2, sm_scale, block_q, block_k):
    out, lse = _fwd(q5, k5, v5, b1, b2, sm_scale, block_q, block_k)
    return out, (q5, k5, v5, b1, b2, out, lse)


def _evo_bwd_rule(sm_scale, block_q, block_k, res, do5):
    q5, k5, v5, b1, b2, out, lse = res
    dq, dk, dv, db1, db2 = _bwd(sm_scale, block_q, block_k,
                                b1 is not None, b2 is not None, res, do5)
    return dq, dk, dv, db1, db2


_evo_core.defvjp(_evo_fwd_rule, _evo_bwd_rule)


def evoformer_attention_pallas(q, k, v,
                               biases: Sequence[Optional[jnp.ndarray]] = (),
                               block_q: int = 128, block_k: int = 128):
    """Fused evoformer attention on [B, S, N, H, D] with reference bias
    shapes (bias1 [B,S,1,1,K], bias2 [B,1,H,Q,K]); see module docstring."""
    if len(biases) > 2:
        raise ValueError("evoformer attention takes at most two biases")
    B, S, Q, H, D = q.shape
    K = k.shape[2]
    b1 = biases[0] if len(biases) > 0 else None
    b2 = biases[1] if len(biases) > 1 else None
    if b1 is not None:
        if b1.shape != (B, S, 1, 1, K):
            raise ValueError(f"bias1 must be [B,S,1,1,K]; got {b1.shape}")
        b1 = b1.reshape(B, S, K).astype(jnp.float32)
    if b2 is not None:
        if b2.shape != (B, 1, H, Q, K):
            raise ValueError(f"bias2 must be [B,1,H,Q,K]; got {b2.shape}")
        b2 = b2.reshape(B, H, Q, K).astype(jnp.float32)
    sm_scale = 1.0 / math.sqrt(D)
    q5 = q.transpose(0, 1, 3, 2, 4)  # [B,S,H,N,D]
    k5 = k.transpose(0, 1, 3, 2, 4)
    v5 = v.transpose(0, 1, 3, 2, 4)
    out = _evo_core(q5, k5, v5, b1, b2, sm_scale, block_q, block_k)
    return out.transpose(0, 1, 3, 2, 4)
