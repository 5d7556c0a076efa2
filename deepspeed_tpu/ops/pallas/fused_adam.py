"""Fused Adam/AdamW Pallas kernel.

TPU equivalent of the reference's multi-tensor-apply fused Adam
(``csrc/adam/multi_tensor_adam.cu``): one kernel updates parameters, exp_avg
and exp_avg_sq in place over a flat buffer, blocked through VMEM.  On TPU,
XLA already fuses the optax update chain; this kernel exists for the
flat-large-buffer path (ZeRO sharded master partitions) where a single pass
with explicit blocking avoids re-materializing intermediates, and as the
numeric reference for the C++ host-offload Adam (ops/cpu/).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                 p_out, m_out, v_out, *, beta1, beta2, eps, weight_decay,
                 adam_w_mode, bias_correction):
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    step = sc_ref[0]  # SMEM scalars: [step, lr] — lr may be a traced
    lr = sc_ref[1]    # schedule value, so it rides in memory, not in code

    if weight_decay != 0.0 and not adam_w_mode:  # L2 into grad (adam mode)
        g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    if bias_correction:
        # beta**step via exp/log: Mosaic has no powf legalization
        import math

        bc1 = 1.0 - jnp.exp(step * math.log(beta1))
        bc2 = 1.0 - jnp.exp(step * math.log(beta2))
        update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    else:
        update = m / (jnp.sqrt(v) + eps)
    if weight_decay != 0.0 and adam_w_mode:  # decoupled decay (adamw)
        update = update + weight_decay * p
    p = p - lr * update

    p_out[...] = p.astype(p_out.dtype)
    m_out[...] = m.astype(m_out.dtype)
    v_out[...] = v.astype(v_out.dtype)


def fused_adam_update(params: jnp.ndarray, grads: jnp.ndarray,
                      exp_avg: jnp.ndarray, exp_avg_sq: jnp.ndarray,
                      step: jnp.ndarray, lr: float, beta1: float = 0.9,
                      beta2: float = 0.999, eps: float = 1e-8,
                      weight_decay: float = 0.0, adam_w_mode: bool = True,
                      bias_correction: bool = True,
                      block: int = 1 << 18) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Flat-buffer Adam step.  All arrays 1-D of equal length; returns
    (new_params, new_exp_avg, new_exp_avg_sq).  ``step`` is the 1-based step
    count (scalar int array).  ``lr`` may be a Python float or a TRACED
    scalar (e.g. a schedule value) — it is carried in SMEM either way."""
    n = params.size
    pad = (-n) % 128
    if pad:
        params, grads = jnp.pad(params, (0, pad)), jnp.pad(grads, (0, pad))
        exp_avg, exp_avg_sq = jnp.pad(exp_avg, (0, pad)), jnp.pad(exp_avg_sq, (0, pad))
    total = params.size
    rows = total // 128
    shape2d = (rows, 128)
    block_rows = min(rows, max(8, block // 128))
    grid = (pl.cdiv(rows, block_rows),)

    args = [a.reshape(shape2d) for a in (params, grads, exp_avg, exp_avg_sq)]
    scalars = jnp.stack([jnp.asarray(step, jnp.float32).reshape(()),
                         jnp.asarray(lr, jnp.float32).reshape(())])

    out = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=beta1, beta2=beta2, eps=eps,
                          weight_decay=weight_decay, adam_w_mode=adam_w_mode,
                          bias_correction=bias_correction),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0))] * 4 +
                 [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((block_rows, 128), lambda i: (i, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(shape2d, params.dtype),
            jax.ShapeDtypeStruct(shape2d, exp_avg.dtype),
            jax.ShapeDtypeStruct(shape2d, exp_avg_sq.dtype),
        ],
        interpret=pallas_interpret(),
    )(*args, scalars)
    p, m, v = (o.reshape(total)[:n] for o in out)
    return p, m, v
