"""Grouped (block-diagonal) expert matmul — Megablocks-style, Pallas TPU.

Reference parity: the grouped MoE GEMMs in
``deepspeed/inference/v2/kernels/cutlass_ops`` (grouped_gemm) and the
dropless-MoE direction of ``moe/sharded_moe.py`` — tokens are sorted by
expert and padded so every row-block belongs to exactly ONE expert; the
kernel then streams blocks through the MXU, selecting each block's expert
weight matrix via a scalar-prefetched block->expert map (the TPU version
of Megablocks' block-diagonal sparsity).

``x``: [P, H] sorted+padded tokens, ``w``: [E, H, F] stacked expert
weights, ``block_expert``: [P / block_rows] int32.  Returns [P, F].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import on_tpu, pallas_interpret


def _tile(dim: int) -> int:
    """Largest MXU-friendly tile dividing ``dim`` (else the whole dim)."""
    return next((t for t in (1024, 512, 256, 128) if dim % t == 0), dim)


def _gmm_kernel(be_ref, x_ref, w_ref, o_ref, acc_ref):
    # w_ref block was selected by the scalar-prefetched index map: it is
    # already a [tk, tn] tile of THIS row block's expert matrix
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray,
                   block_expert: jnp.ndarray, block_rows: int = 128,
                   impl: str = "auto") -> jnp.ndarray:
    """Block-grouped ``x @ w[block_expert[block]]``.

    Every ``block_rows`` rows of ``x`` share one expert.  P must be a
    multiple of ``block_rows`` (the no-drop router pads per expert).

    ``impl="auto"`` is the kernel on TPU — never the XLA einsum — and the
    einsum on the CPU test tier, where interpreting the kernel would only
    slow the tests.  The kernel is FORWARD-ONLY (no VJP yet): training a
    dropless MoE on the chip fails at differentiation instead of quietly
    taking the einsum."""
    P, H = x.shape
    E, _, F = w.shape
    assert P % block_rows == 0, (P, block_rows)
    n_blocks = P // block_rows

    if impl == "xla" or (impl == "auto" and not on_tpu()):
        wb = w[block_expert]  # [n_blocks, H, F]
        xb = x.reshape(n_blocks, block_rows, H)
        return jnp.einsum("bph,bhf->bpf", xb.astype(jnp.float32),
                          wb.astype(jnp.float32)).reshape(P, F).astype(x.dtype)

    # tiled over the contraction (H) and output (F) dims with an fp32
    # accumulator: VMEM holds [tk, tn] of the expert matrix, not all of it
    # (one Mixtral expert matrix is 117 MB in bf16)
    tk, tn = _tile(H), _tile(F)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_blocks, F // tn, H // tk),
        in_specs=[
            pl.BlockSpec((block_rows, tk), lambda i, j, k, be: (i, k)),
            pl.BlockSpec((1, tk, tn), lambda i, j, k, be: (be[i], k, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, tn), lambda i, j, k, be: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_rows, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, F), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_grouped_matmul",
    )(block_expert, x, w)
