"""Flash attention (Pallas TPU kernel, custom VJP).

The TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/*.cu`` softmax/attention path and
``csrc/transformer/inference/csrc/softmax.cu``): blocked online-softmax
forward that never materializes the [S, S] score matrix, and a
recompute-based backward (dq / dk / dv kernels) using the saved
log-sum-exp — the memory behavior that makes long sequences feasible.

Layout: kernels work on [BH, S, D] (batch*heads merged); the public API
takes [B, S, NH, D] to match models/transformer.py.  Every kernel walks a
(q tile, k tile) grid with its running state in VMEM scratch, so VMEM
holds tiles only and the sequence length is bounded by HBM, not VMEM.
A backward tile is classed once, from the scalars of its position
(``_tile_class``): *skipped* (wholly above the diagonal: no body, no DMA),
*interior* (causal with ``k_start + bk - 1 <= q_start`` or not causal;
``k_start + bk <= seq_k``; ``q_start + bq <= seq_q`` — every pair visible, so
the body has no iota, compare or select) or *edge* (the rest: the masked body).
The forward masks every computed tile: there the mask's arithmetic hides under
the row reductions (all tiles bare read - 0.1 % on the v5e: PERF.md §6, PR 55).
``impl="jax"`` selects the stock jax pallas kernel
(``jax.experimental.pallas.ops.tpu.flash_attention``) for comparison.
Compiled on TPU, interpreted on the CPU test tier (utils/platform.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.activation_checkpointing.checkpointing import (
    FLASH_LSE, FLASH_OUT)
from ...utils.platform import pallas_interpret

NEG_INF = -1e30


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


def _dot(a, b, contract):
    """MXU matmul in the operands' dtype, fp32 accumulate."""
    return jax.lax.dot_general(a, b, ((contract[0], contract[1]), ((), ())),
                               preferred_element_type=jnp.float32)


def _scores(q, k, sl_ref, head, rows, cols, *, sm_scale, causal, alibi,
            seq_q, seq_k, window=0, k_first=None, block=0, masked=True):
    """fp32 scores [bq, bk] of one tile + the validity mask; an interior tile
    (``masked=False``) has neither mask nor select.
    ``rows``/``cols`` are absolute positions; rows past ``seq_q`` and cols
    past ``seq_k`` are block padding.  ``window``: a row sees itself and the
    ``window - 1`` columns before it, none before ``k_first``.  ``block`` (a
    power of two): the causal mask is between blocks of that many positions
    and a row sees the whole of its own block."""
    s = _dot(q, k, ((1,), (1,))) * sm_scale
    if alibi:
        # ALiBi from block indices: no [S, S] bias materialization
        s = s - sl_ref[head] * (rows - cols).astype(jnp.float32)
    if not masked:
        return s, None
    valid = cols < seq_k
    if seq_q is not None:
        valid = valid & (rows < seq_q)
    if causal:
        valid = valid & ((rows | (block - 1) if block else rows) >= cols)
    if window:
        valid = valid & (rows - cols < window) & (cols >= k_first)
    return jnp.where(valid, s, NEG_INF), valid


def _tile_class(q_start, k_start, bq, bk, *, causal, seq_q, seq_k):
    """(computed, interior) of the backward tile at (``q_start``,
    ``k_start``), from scalars alone (traced in a kernel, Python ints in
    ``tile_classes``).  Not computed: wholly above the diagonal.  Interior:
    every (row, column) is visible — wholly under the diagonal and inside
    both sequences; a computed tile that is not interior is an edge tile."""
    q_last, k_last = q_start + bq - 1, k_start + bk - 1
    interior = (q_last < seq_q) & (k_last < seq_k)
    if not causal:
        return True, interior
    return k_start <= q_last, interior & (k_last <= q_start)


def _run_tile(cls, body):
    """``body(masked)`` as the tile's class asks: not at all, bare or masked."""
    computed, interior = cls
    pl.when(computed & interior)(functools.partial(body, False))
    pl.when(computed & jnp.logical_not(interior))(
        functools.partial(body, True))


def tile_classes(seq_q, seq_k, block_q, block_k, causal=True, valid_q=None,
                 valid_k=None):
    """(skipped, interior, edge): how many tiles of each class one head of a
    backward call has — the kernels' own predicate over their grid, on the
    host.  ``seq_q`` / ``seq_k`` are the lengths the grid covers, ``valid_q``
    / ``valid_k`` the rows and keys that are not padding."""
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    valid_q = seq_q if valid_q is None else valid_q
    valid_k = seq_k if valid_k is None else valid_k
    skipped = interior = edge = 0
    for q_start in range(0, seq_q, bq):
        for k_start in range(0, seq_k, bk):
            computed, bare = _tile_class(q_start, k_start, bq, bk,
                                         causal=causal, seq_q=valid_q,
                                         seq_k=valid_k)
            skipped += not computed
            interior += computed and bare
            edge += computed and not bare
    return skipped, interior, edge


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(off_ref, sl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal, seq_k, alibi,
                window=0, sink=False, block=0):
    """Grid (B*NH, nq, nk), k innermost: one [bq, bk] score tile per step,
    the online-softmax state (m, l, acc) carried in VMEM scratch across
    the k axis — VMEM holds tiles, never a whole sequence."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    # program ids are read at the top level: the interpreter does not
    # resolve them inside a pl.when body
    head, jk = pl.program_id(0), pl.program_id(2)
    # chunked prefill: query i is GLOBAL position off + q_start + i (keys
    # are pool slots at their global positions); off is 0 in training
    q_start = off_ref[0] + pl.program_id(1) * bq
    k_start = jk * bk

    @pl.when(jk == 0)
    def _():
        if sink:
            # ``sl_ref`` holds the heads' sinks: one more column of the
            # softmax with no value, which starts the maximum and the sum
            m_scr[...] = jnp.full_like(m_scr, sl_ref[head])
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile():
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s, _ = _scores(q_ref[0], k_ref[0], sl_ref, head, rows, cols, sm_scale=sm_scale, causal=causal, alibi=alibi,
                       seq_q=None, seq_k=seq_k, window=window,
                       k_first=off_ref[1] if window else None,
                       **({"block": block} if block else {}))
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + _dot(
            p.astype(v_ref.dtype), v_ref[0], ((1,), (0,)))
        m_scr[...] = m_new

    if causal and window and sink:
        # nor do k tiles wholly before the first row's window (taken with a
        # sink alone: the window kernel without one stays the program the
        # differential layers have run since PR 34)
        pl.when((k_start <= q_start + bq - 1)
                & (k_start + bk > q_start - window + 1))(tile)
    elif causal:
        # k tiles wholly above the diagonal contribute nothing (and the
        # index map re-uses the diagonal tile for them: no DMA either)
        pl.when(k_start <= q_start + bq - 1)(tile)
    else:
        tile()

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l)  # [bq, 1]


def _fwd(q, k, v, alibi_arr, offset_arr, sm_scale, causal, block_q, block_k,
         valid_k=None, q_per_kv=1, alibi=False, window=0, sink=False,
         block=0):
    """q: [B*NH, Sq, D]; k/v: [B*KVH, Sk, D] with NH = KVH * q_per_kv —
    GQA reads each kv head once via the index map instead of materializing
    the repeat (the reference's kv-replication copy).  ``alibi_arr``:
    [B*NH] fp32 slopes and ``offset_arr``: [1] int32 query offset (with a
    ``window``, [2]: the offset and the first key a query may see), both
    scalar memory.  ``v`` may be narrower than ``q`` and ``k``: the output
    has its width.  ``sink``: ``alibi_arr`` holds a sink a head instead.
    ``block``: the block mask (``_scores``); the offset and the q tile are
    whole blocks, so a tile's last row bounds what its rows see as it does
    under the causal mask and the same k tiles are skipped."""
    bh, seq_q, d = q.shape
    dv = v.shape[2]
    seq_k = k.shape[1]
    valid_k = valid_k if valid_k is not None else seq_k
    bq = min(block_q, seq_q)
    bk = min(block_k, seq_k)
    nk = pl.cdiv(seq_k, bk)
    g = q_per_kv

    def kv_map(b, i, j, off):
        if causal:  # clamp to the diagonal tile: skipped tiles move no data
            j = jnp.minimum(j, jnp.minimum(
                (off[0] + i * bq + bq - 1) // bk, nk - 1))
        return (b // g, j, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          seq_k=valid_k, alibi=alibi,
                          sink=sink,
                          **({"window": window} if window else {}),
                          **({"block": block} if block else {})),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, pl.cdiv(seq_q, bq), nk),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bq, d), lambda b, i, j, off: (b, i, 0)),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, dv), lambda b, i, j, off: (b, i, 0)),
                pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=pallas_interpret(),
        name="dstpu_flash_fwd",
    )(offset_arr, alibi_arr, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels (recompute p from q,k + lse)
# ---------------------------------------------------------------------------
def _bwd_tile(q, k, v, do, lse, delta, sl_ref, head, q_start, k_start, *,
              masked, sm_scale, causal, alibi, seq_q, seq_k):
    """(p, ds) of one tile, both fp32 [bq, bk].  Padded q rows carry
    garbage q/lse and padded k cols garbage k — both masked to zero (an
    interior tile, ``masked=False``, has neither and builds no positions
    but for ALiBi)."""
    rows = cols = None
    if masked or alibi:
        bq, bk = q.shape[0], k.shape[0]
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    s, valid = _scores(q, k, sl_ref, head, rows, cols, sm_scale=sm_scale,
                       causal=causal, alibi=alibi, seq_q=seq_q, seq_k=seq_k,
                       masked=masked)
    p = jnp.exp(s - lse)
    if masked:
        p = jnp.where(valid, p, 0.0)
    dp = _dot(do, v, ((1,), (1,)))
    return p, p * (dp - delta) * sm_scale


def _bwd_dq_kernel(sl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, sm_scale, causal, seq_q, seq_k, alibi):
    """Grid (B*NH, nq, nk), k innermost; dq accumulates in fp32 scratch."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    head, jk = pl.program_id(0), pl.program_id(2)
    q_start = pl.program_id(1) * bq
    k_start = jk * bk

    @pl.when(jk == 0)
    def _():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def tile(masked):
        _, ds = _bwd_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                          lse_ref[0], delta_ref[0], sl_ref, head, q_start,
                          k_start, masked=masked,
                          sm_scale=sm_scale, causal=causal, alibi=alibi,
                          seq_q=seq_q, seq_k=seq_k)
        dq_scr[...] += _dot(ds.astype(k_ref.dtype), k_ref[0], ((1,), (0,)))

    _run_tile(_tile_class(q_start, k_start, bq, bk, causal=causal,
                          seq_q=seq_q, seq_k=seq_k), tile)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(sl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    seq_q, seq_k, q_per_kv, alibi):
    """Grid (B*KVH, nk, q_per_kv, nq): the dk/dv output block (indexed
    (bkv, jk)) is revisited across the two inner axes — every grouped q
    head and every q tile accumulates into fp32 VMEM scratch (not the
    output dtype — bf16 accumulation would lose precision across the
    group) and the cast happens once at the end."""
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    gi, iq = pl.program_id(2), pl.program_id(3)
    head = pl.program_id(0) * q_per_kv + gi
    q_start = iq * bq
    k_start = pl.program_id(1) * bk

    @pl.when((gi == 0) & (iq == 0))
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def tile(masked):
        p, ds = _bwd_tile(q_ref[0], k_ref[0], v_ref[0], do_ref[0],
                          lse_ref[0], delta_ref[0], sl_ref, head, q_start,
                          k_start, masked=masked,
                          sm_scale=sm_scale, causal=causal, alibi=alibi,
                          seq_q=seq_q, seq_k=seq_k)
        dv_scr[...] += _dot(p.astype(do_ref.dtype), do_ref[0], ((0,), (0,)))
        dk_scr[...] += _dot(ds.astype(q_ref.dtype), q_ref[0], ((0,), (0,)))

    # q tiles wholly before this k tile are the skipped ones here
    _run_tile(_tile_class(q_start, k_start, bq, bk, causal=causal,
                          seq_q=seq_q, seq_k=seq_k), tile)

    @pl.when((gi == q_per_kv - 1) & (iq == pl.num_programs(3) - 1))
    def _():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, valid_q, valid_k, q_per_kv,
         bwd_block_q, bwd_block_k, alibi, res, do):
    q, k, v, alibi_arr, out, lse = res
    bh, seq_q, d = q.shape
    bkv = k.shape[0]
    seq_k = k.shape[1]
    # the fwd-optimal tiling need not be bwd-optimal (dq/dkv kernels keep
    # different residents in VMEM); 0 = inherit the forward blocks.
    # Clamp against the TRUE lengths (valid_*), not the padded seq_*: the
    # wrapper's lcm padding used min(bwd_block, true_len), and the
    # effective tile here must match it so every block divides the padding
    bq = min(bwd_block_q or block_q, valid_q, seq_q)
    bk = min(bwd_block_k or block_k, valid_k, seq_k)
    nq, nk = pl.cdiv(seq_q, bq), pl.cdiv(seq_k, bk)
    g = q_per_kv

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [BH, Sq, 1]
    kw = dict(sm_scale=sm_scale, causal=causal, seq_q=valid_q, seq_k=valid_k,
              alibi=alibi)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def kv_map(b, i, j):
        if causal:  # as in the forward: skipped tiles re-use the diagonal's
            j = jnp.minimum(j, jnp.minimum((i * bq + bq - 1) // bk, nk - 1))
        return (b // g, j, 0)

    q_spec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    row_spec = pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(bh, nq, nk),
        in_specs=[smem, q_spec, pl.BlockSpec((1, bk, d), kv_map),
                  pl.BlockSpec((1, bk, d), kv_map), q_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=pallas_interpret(),
        name="dstpu_flash_bwd_dq",
    )(alibi_arr, q, k, v, do, lse, delta)

    def q_map(b, j, gi, i):
        if causal:  # first q tile that reaches this k tile
            i = jnp.maximum(i, jnp.minimum((j * bk) // bq, nq - 1))
        return (b * g + gi, i, 0)

    kv_spec = pl.BlockSpec((1, bk, d), lambda b, j, gi, i: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_per_kv=g, **kw),
        grid=(bkv, nk, g, nq),
        in_specs=[smem, pl.BlockSpec((1, bq, d), q_map), kv_spec, kv_spec,
                  pl.BlockSpec((1, bq, d), q_map),
                  pl.BlockSpec((1, bq, 1), q_map),
                  pl.BlockSpec((1, bq, 1), q_map)],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=pallas_interpret(),
        name="dstpu_flash_bwd_dkv",
    )(alibi_arr, q, k, v, do, lse, delta)
    # alibi slopes are fixed constants: zero cotangent
    return dq, dk, dv, jnp.zeros_like(alibi_arr)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10,
                                                    11, 12, 13))
def _flash_bhsd(q, k, v, alibi_arr, sm_scale, causal, block_q, block_k,
                valid_q, valid_k, q_per_kv, bwd_block_q, bwd_block_k, alibi):
    out, _ = _fwd(q, k, v, alibi_arr, jnp.zeros((1,), jnp.int32), sm_scale,
                  causal, block_q, block_k, valid_k, q_per_kv, alibi=alibi)
    return out


def _flash_fwd_rule(q, k, v, alibi_arr, sm_scale, causal, block_q, block_k,
                    valid_q, valid_k, q_per_kv, bwd_block_q, bwd_block_k,
                    alibi):
    out, lse = _fwd(q, k, v, alibi_arr, jnp.zeros((1,), jnp.int32), sm_scale,
                    causal, block_q, block_k, valid_k, q_per_kv, alibi=alibi)
    # a recomputed block that keeps these two replays no forward kernel
    # (the backward kernels read both: one without the other buys nothing)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, alibi_arr, out, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, valid_q, valid_k,
                    q_per_kv, bwd_block_q, bwd_block_k, alibi, res, do):
    return _bwd(sm_scale, causal, block_q, block_k, valid_q, valid_k,
                q_per_kv, bwd_block_q, bwd_block_k, alibi, res, do)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q, k, v, causal: bool = True, segment_mask=None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512, impl: str = "pallas",
                    bwd_block_q: int = 0, bwd_block_k: int = 0,
                    alibi_slopes=None, q_offset=None, window: int = 0,
                    k_first=None, sink=None, block: int = 0):
    """Public API on [B, S, NH, D] (matching models/transformer.py).

    GQA-native: k/v may carry KVH < NH heads (NH % KVH == 0) — each kv
    head is read once via the kernel's index map instead of materializing
    the NH/KVH-fold repeat in HBM.

    ``bwd_block_q``/``bwd_block_k`` tile the BACKWARD kernels independently
    of the forward (0 = inherit): the dq/dkv kernels keep different
    residents in VMEM, so the fwd-optimal tiling need not be bwd-optimal.

    ``segment_mask``: optional [B, S_k] padding mask (1 = keep); runs the
    XLA path when given, with a warning (masked flash variant: future work).

    ``alibi_slopes``: optional [NH] per-head ALiBi slopes — the bias is
    built INSIDE the kernels from block indices (score -= slope*(i-j)),
    never materializing [S, S] (bloom-family long-context training).
    Assumes absolute in-kernel indices == token positions (unsharded or
    Ulysses-regathered sequence, same contract as causal).

    ``q_offset``: optional RUNTIME scalar — query i sits at absolute
    position ``q_offset + i`` while keys keep their buffer index as
    their position (chunked prefill over a position-ordered KV window).
    FORWARD-ONLY: the offset is not threaded through the backward
    kernels, so this path defines no VJP.

    ``window`` (with ``q_offset``, forward-only): a query sees itself and
    the ``window - 1`` keys before it, and no key before the RUNTIME scalar
    ``k_first`` (a window layer's chunk attends ``[its ring in position
    order | the chunk]``, and a ring that holds fewer than ``window``
    positions is masked from the front).

    Forward-only (``q_offset``) too: ``v`` may be ``[B, Sk, KVH, DV]`` with
    ``DV`` another width than the keys' (the output is ``[B, Sq, NH, DV]``),
    and ``sink`` ``[NH]`` float32 is one more column of each head's softmax
    that has no value — it joins the running maximum and the denominator, so
    a row's probabilities sum to less than 1.

    ``block`` (with ``q_offset``, forward-only; a power of two): the mask is
    causal between blocks of ``block`` positions at absolute multiples of it
    and bidirectional inside one — query ``i`` sees key ``j`` iff ``j //
    block <= i // block`` (generation by diffusion over blocks).  The offset
    and the q tile must be whole blocks.
    """
    B, Sq, NH, D = q.shape
    KVH, DV = k.shape[2], v.shape[3]
    if q_offset is None and (DV != D or sink is not None):
        raise ValueError("a value width of its own and a sink exist in the "
                         "forward-only kernel (q_offset) alone: the backward "
                         "kernels have neither")
    if sink is not None and alibi_slopes is not None:
        raise ValueError("a sink and ALiBi slopes share the kernel's "
                         "per-head operand: one or the other")
    if segment_mask is not None:
        from ...models.transformer import _repeat_kv, xla_attention
        from ...utils.logging import warning_once

        warning_once(
            "flash_attention: a padding mask was given and the kernel has no "
            "masked variant — this call runs xla_attention, which "
            "materializes the [B, NH, S, S] scores")
        bias = None
        if alibi_slopes is not None:
            # END-align queries like xla_attention's causal mask (tril with
            # k=Sk-Sq): query i sits at absolute position Sk-Sq+i, so a
            # decode-style Sq < Sk call penalizes distance correctly
            Sk_ = k.shape[1]
            rel = ((Sk_ - Sq + jnp.arange(Sq))[:, None]
                   - jnp.arange(Sk_)[None, :]).astype(jnp.float32)
            bias = -jnp.asarray(alibi_slopes)[None, :, None, None] * rel
        return xla_attention(q, _repeat_kv(k, NH // KVH),
                             _repeat_kv(v, NH // KVH), causal, segment_mask,
                             bias=bias)
    Sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if NH % KVH != 0:
        raise ValueError(f"n_heads {NH} not a multiple of kv heads {KVH}")
    q_per_kv = NH // KVH
    if impl == "jax" and alibi_slopes is not None:
        raise ValueError("impl='jax' (stock kernel) has no ALiBi input; "
                         "use the default pallas impl")
    if impl == "jax":  # stock kernel for comparison
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_fa)

        from ...models.transformer import _repeat_kv

        out = jax_fa(q.transpose(0, 2, 1, 3),
                     _repeat_kv(k, q_per_kv).transpose(0, 2, 1, 3),
                     _repeat_kv(v, q_per_kv).transpose(0, 2, 1, 3),
                     causal=causal, sm_scale=scale)
        return out.transpose(0, 2, 1, 3)

    qh = q.transpose(0, 2, 1, 3).reshape(B * NH, Sq, D)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, D)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KVH, Sk, DV)
    # pad to block multiples: pl.ds clamps out-of-bounds starts, which would
    # silently mislabel columns in edge blocks; masks use the true lengths.
    # The padded length must be a multiple of BOTH the fwd and bwd tiles.
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    pad_q = (-Sq) % (math.lcm(bq, min(bwd_block_q, Sq)) if bwd_block_q
                     else bq)
    pad_k = (-Sk) % (math.lcm(bk, min(bwd_block_k, Sk)) if bwd_block_k
                     else bk)
    if pad_q or pad_k:
        qh = jnp.pad(qh, ((0, 0), (0, pad_q), (0, 0)))
        kh = jnp.pad(kh, ((0, 0), (0, pad_k), (0, 0)))
        vh = jnp.pad(vh, ((0, 0), (0, pad_k), (0, 0)))
    if alibi_slopes is not None or sink is not None:
        sl = jnp.tile(jnp.asarray(
            sink if alibi_slopes is None else alibi_slopes, jnp.float32), B)
    else:
        sl = jnp.zeros((B * NH,), jnp.float32)
    if window and (q_offset is None or not causal):
        raise ValueError("window: the mask exists in the causal forward-only "
                         "kernel (q_offset) alone")
    if block and (q_offset is None or not causal or window
                  or block & (block - 1) or min(block_q, Sq) % block):
        raise ValueError("block: the block mask exists in the causal "
                         "forward-only kernel (q_offset) alone, without a "
                         "window, for a power of two that divides the q tile")
    if q_offset is not None:
        # forward-only inference path (no custom VJP)
        off = jnp.asarray(q_offset, jnp.int32).reshape(1)
        if window:
            off = jnp.concatenate([off, jnp.asarray(
                0 if k_first is None else k_first, jnp.int32).reshape(1)])
        out, _ = _fwd(qh, kh, vh, sl, off, scale,
                      causal, block_q, block_k, Sk, q_per_kv,
                      alibi=alibi_slopes is not None,
                      sink=sink is not None,
                      **({"window": int(window)} if window else {}),
                      **({"block": int(block)} if block else {}))
    else:
        out = _flash_bhsd(qh, kh, vh, sl, scale, causal, block_q, block_k,
                          Sq, Sk, q_per_kv, bwd_block_q, bwd_block_k,
                          alibi_slopes is not None)
    out = out[:, :Sq]
    return out.reshape(B, NH, Sq, DV).transpose(0, 2, 1, 3)

