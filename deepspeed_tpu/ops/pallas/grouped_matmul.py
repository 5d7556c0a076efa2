"""Grouped (block-diagonal) expert matmul — Megablocks-style, Pallas TPU.

Reference parity: the grouped MoE GEMMs in
``deepspeed/inference/v2/kernels/cutlass_ops`` (grouped_gemm) and the
dropless-MoE direction of ``moe/sharded_moe.py`` — tokens are sorted by
expert and padded so every row-block belongs to exactly ONE expert; the
kernel then streams blocks through the MXU, selecting each block's expert
weight matrix via a scalar-prefetched block->expert map (the TPU version
of Megablocks' block-diagonal sparsity).

``x``: [P, H] sorted+padded tokens, ``w``: [E, H, F] stacked expert
weights, ``block_expert``: [P / block_rows] int32, ``n_real``: how many of
those blocks, from the first on, hold a row anyone reads.  Returns [P, F].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.activation_checkpointing.checkpointing import (
    GROUPED_MATMUL_OUT)
from ...utils.platform import on_tpu, pallas_interpret

#: bytes of one weight tile ``[H, tn]``.  Two are in flight (the pipeline's
#: double buffer) beside two row blocks and two output blocks, and a v5e
#: core has 128 MiB of VMEM.  Wider is faster as far as measured (PERF.md
#: §6, PR 30): a Solar-Open2 expert matrix (10.5 MB) is one tile, read in
#: whole rows at 92 % of the HBM's rate; Mixtral's 14336 x 4096 in tiles
#: of 512 columns
_W_TILE_BYTES = 16 * 2 ** 20


def expert_block_rows(picks_per_expert: float, dtype) -> int:
    """The row-block height of the sorted and padded buffer, from what a
    call knows before it runs: the picks an expert expects (``T * top_k /
    num_experts``) and the activations' dtype.  The power of two that holds
    twice the expected picks — a touched expert gets more than the mean —
    between the dtype's sublane tile (16 rows of bf16, 8 of float32: the
    least a block can be) and the MXU's 128.  A block too low costs grid
    steps and nothing else (an expert's blocks share one fetch of its
    matrix); one too high costs the rows of every touched expert's last
    block, in the scatter, the activation and the kernel's row reads."""
    sublane = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    want = max(1, int(2 * picks_per_expert))
    return min(128, max(sublane, 1 << (want - 1).bit_length()))


def _out_tile(h: int, f: int, itemsize: int) -> int:
    """The widest tile of the output dim that divides it in whole lanes and
    keeps ``[h, tile]`` of the expert matrix within ``_W_TILE_BYTES`` (else
    128 lanes, or the whole of a dim that has no such divisor)."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and h * t * itemsize <= _W_TILE_BYTES]
    return max(fits, default=128)


def _gmm_kernel(be_ref, nr_ref, x_ref, w_ref, o_ref, *, transpose_w):
    # w_ref is the tile of THIS row block's expert matrix, selected by the
    # scalar-prefetched index map ([H, tn], or [tn, F] of its rows for the
    # product against the transpose); a block past the real ones holds the
    # previous step's tiles (nothing was fetched) and does nothing
    @pl.when(pl.program_id(1) < nr_ref[0])
    def _():
        if transpose_w:
            y = jax.lax.dot_general(x_ref[...], w_ref[0],
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            y = jnp.dot(x_ref[...], w_ref[0],
                        preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def _gmm_dw_kernel(be_ref, nr_ref, x_ref, dy_ref, o_ref, acc_ref):
    # one expert's blocks follow each other, so its [H, tn] tile of the
    # result stays in place while they are summed into the float32 scratch;
    # the expert's last block writes it.  Blocks past the real ones are
    # neither fetched nor read
    i, nr = pl.program_id(1), nr_ref[0]

    @pl.when(i < nr)
    def _():
        e = be_ref[i]
        first = jnp.logical_or(i == 0, be_ref[jnp.maximum(i - 1, 0)] != e)
        last = jnp.logical_or(
            i == nr - 1,
            be_ref[jnp.minimum(i + 1, pl.num_programs(1) - 1)] != e)
        part = jax.lax.dot_general(x_ref[...], dy_ref[...],
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

        @pl.when(first)
        def _():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _():
            acc_ref[...] += part

        @pl.when(last)
        def _():
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _last_real(i, nr):  # the last real block stands in for the ones past it
    return jnp.minimum(i, jnp.maximum(nr[0] - 1, 0))


def _tile(j, nr):  # no real block: one tile for the whole grid
    return jnp.where(nr[0] > 0, j, 0)


def _gmm_call(x, w, block_expert, n_real, block_rows, transpose_w):
    """``x @ w[e]`` (or ``x @ w[e].T``) over the real blocks.  The whole
    contraction in one step (VMEM holds one tile of the expert matrix, not
    all of it: one Mixtral matrix is 117 MB in bf16), output tiles
    outermost: the grid is (tiles) * n_blocks steps, and a step that does
    nothing costs about a tenth of a microsecond."""
    P, K = x.shape
    n_blocks = P // block_rows
    N = w.shape[1] if transpose_w else w.shape[2]
    tn = _out_tile(K, N, w.dtype.itemsize)
    if transpose_w:
        w_spec = pl.BlockSpec(
            (1, tn, K), lambda j, i, be, nr: (be[_last_real(i, nr)],
                                              _tile(j, nr), 0))
    else:
        w_spec = pl.BlockSpec(
            (1, K, tn), lambda j, i, be, nr: (be[_last_real(i, nr)], 0,
                                              _tile(j, nr)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N // tn, n_blocks),
        in_specs=[
            pl.BlockSpec((block_rows, K),
                         lambda j, i, be, nr: (_last_real(i, nr), 0)),
            w_spec,
        ],
        out_specs=pl.BlockSpec(
            (block_rows, tn),
            lambda j, i, be, nr: (_last_real(i, nr), _tile(j, nr))),
    )
    in_flight = 2 * (K * tn * w.dtype.itemsize
                     + block_rows * (K + tn) * x.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=in_flight + 16 * 2 ** 20),
        interpret=pallas_interpret(),
        name="dstpu_grouped_matmul_dx" if transpose_w
        else "dstpu_grouped_matmul",
    )(block_expert, n_real, x, w)


def _gmm_dw_call(x, dy, block_expert, n_real, block_rows, n_experts, dtype):
    """Per expert, ``x_blocks^T @ dy_blocks`` summed over its real blocks:
    ``[E, H, F]``.  An expert without a block is never visited: its tiles
    come back undefined and the caller zeroes them."""
    P, H = x.shape
    F = dy.shape[1]
    n_blocks = P // block_rows
    itemsize = jnp.dtype(dtype).itemsize
    tn = _out_tile(H, F, itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(F // tn, n_blocks),
        in_specs=[
            pl.BlockSpec((block_rows, H),
                         lambda j, i, be, nr: (_last_real(i, nr), 0)),
            pl.BlockSpec((block_rows, tn),
                         lambda j, i, be, nr: (_last_real(i, nr),
                                               _tile(j, nr))),
        ],
        out_specs=pl.BlockSpec(
            (1, H, tn), lambda j, i, be, nr: (be[_last_real(i, nr)], 0,
                                              _tile(j, nr))),
        scratch_shapes=[pltpu.VMEM((H, tn), jnp.float32)],
    )
    in_flight = (H * tn * (4 + 2 * itemsize)
                 + 2 * block_rows * (H + tn) * x.dtype.itemsize)
    return pl.pallas_call(
        _gmm_dw_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, H, F), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=in_flight + 16 * 2 ** 20),
        interpret=pallas_interpret(),
        name="dstpu_grouped_matmul_dw",
    )(block_expert, n_real, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(x, w, block_expert, n_real, block_rows):
    return _gmm_call(x, w, block_expert, n_real, block_rows, False)


def _gmm_fwd(x, w, block_expert, n_real, block_rows):
    y = checkpoint_name(_gmm(x, w, block_expert, n_real, block_rows),
                        GROUPED_MATMUL_OUT)
    return y, (x, w, block_expert, n_real)


def _gmm_bwd(block_rows, res, dy):
    x, w, block_expert, n_real = res
    dx = _gmm_call(dy, w, block_expert, n_real, block_rows, True)
    dw = _gmm_dw_call(x, dy, block_expert, n_real, block_rows, w.shape[0],
                      w.dtype)
    # an expert no real block names was never visited by the kernel
    n_blocks = block_expert.shape[0]
    touched = jnp.zeros((w.shape[0],), jnp.bool_).at[block_expert].max(
        jnp.arange(n_blocks) < n_real[0])
    dw = jnp.where(touched[:, None, None], dw, jnp.zeros((), dw.dtype))
    return dx, dw, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray,
                   block_expert: jnp.ndarray, block_rows: int = 128,
                   impl: str = "auto",
                   n_real: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Block-grouped ``x @ w[block_expert[block]]``.

    Every ``block_rows`` rows of ``x`` share one expert.  P must be a
    multiple of ``block_rows`` (the no-drop router pads per expert).

    ``n_real`` (an int32 scalar; None: every block): the first ``n_real``
    blocks are the ones that hold rows — ``sort_pad_by_expert`` lays them
    first and counts them.  The kernel does their work and no other: a grid
    step past them computes nothing and fetches nothing (its index maps
    return the tiles the last real block held, and an unchanged block is
    not fetched again), and blocks of one expert that follow each other
    share one fetch of each of its tiles (the output tiles are the outer
    grid axis).  THE ROWS OF THE BLOCKS PAST ``n_real`` ARE NOT WRITTEN:
    they hold whatever the buffer held, which may be NaN.  Gather only the
    rows that were scattered in.  With ``n_real`` 0 the call reads one tile
    (the pipeline's first fetch) and no more.

    ``impl="auto"`` is the kernel on TPU — never the XLA einsum — and the
    einsum on the CPU test tier, where interpreting the kernel would only
    slow the tests.

    The kernel differentiates (``custom_vjp``) into two more kernels over
    the same real blocks: ``dstpu_grouped_matmul_dx``, the same product
    against the transposed tiles (``dy @ w[e].T``; the rows of the blocks
    past ``n_real`` of ``dx`` are as undefined as the forward's), and
    ``dstpu_grouped_matmul_dw``, per expert ``x_blocks^T @ dy_blocks``
    summed in float32 over the expert's blocks.  Neither reads a row of a
    block past ``n_real``, so what those rows hold — NaN included — reaches
    no gradient; an expert without a pick gets a zero gradient."""
    P, H = x.shape
    E, _, F = w.shape
    assert P % block_rows == 0, (P, block_rows)
    n_blocks = P // block_rows

    if impl == "xla" or (impl == "auto" and not on_tpu()):
        wb = w[block_expert]  # [n_blocks, H, F]
        xb = x.reshape(n_blocks, block_rows, H)
        return jnp.einsum("bph,bhf->bpf", xb.astype(jnp.float32),
                          wb.astype(jnp.float32)).reshape(P, F).astype(x.dtype)

    n_real = jnp.asarray(n_blocks if n_real is None else n_real,
                         jnp.int32).reshape(1)
    return _gmm(x, w, block_expert, n_real, block_rows)
