"""Paged decode attention (Pallas TPU kernel).

The TPU-native replacement for the reference's ragged decode kernels
(``inference/v2/kernels/ragged_ops``): one query token per sequence
attends over that sequence's KV *pages in place*.  The pools stay in HBM
as the engine carries them (``memory_space=pl.ANY``: no block, no
per-layer slice) and the kernel walks **the pages a row has, not the
table it was given**:

- one grid step a few decode rows (``grid=(B // rows,)``, ``rows`` the
  largest divisor of B up to 8), walked inside the kernel as ONE sequence
  of (row, block) items: a ``fori_loop`` over the blocks of
  ``nb = pages_per_block(...)`` pages (≈ 256 tokens) of the rows that have
  any, ``n_blocks(length)`` a row — none for an inactive row (length 0),
  which is never visited, reads nothing and returns zeros;
- each item's live pages are fetched ``k_pool[layer, page_table[b, j]]``
  → VMEM by explicit async copies, one a page, into a two-slot ring; the
  next item's copies — the same row's next block, or the next active
  row's first — are started before this item's are waited for; pages of a
  row's last block past its length are not fetched (their V rows are
  zeroed, their scores masked);
- a block is attended once for all its K/V heads, in three phases: the
  KVH score products ``[G, D] x [D, T]`` (``T = nb*ps``) issued back to
  back into one ``[NH, T]`` float32 tile, nothing between them, so no
  product waits for another head's reductions; ONE online-softmax update
  on the stacked scores (whole registers; mask, ALiBi term, float32
  statistics); the KVH value products ``[G, T] x [T, D]`` back to back
  into one ``[NH, D]`` tile and one accumulate.  K/V in the dtype stored.
  A chain a head — product, reductions on a ``[G, T]`` sliver, scratch
  updates, product — held the kernel at a third of the bytes' roofline;
  in phases the arithmetic of a block hides behind its copies (PERF.md,
  PR 35).

So device time grows with the visible pages and with nothing else: the
table's width (``max_pages_per_seq``) costs nothing and ``max_seqs`` a
grid step of no work for every ``rows`` empty decode slots.

Layout: q [B, NH, D] (query head ``n`` reads kv head ``n // G``);
pools [L, P, ps, KVH*D] as the engine stores them (KVH and D merged: head
``h`` of a page is the lane slice ``[h*D, (h+1)*D)``, a tile-aligned view
for D a multiple of 128); page_table [B, MP] int32 (entries past a row's
pages are never read); positions [B] int32 (slot of the CURRENT token —
slots > position are masked); ``active`` [B] bool (a row that is not
active has length 0).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret

NEG_INF = -1e30

#: tokens a block aims at and the VMEM one slot of one pool may take; K and
#: V double-buffered are four such slots.  256 tokens: a block's softmax
#: and its scratch traffic are paid once for two lane tiles of keys, and
#: the arithmetic hides whole behind the copies (PERF.md, PR 35)
_BLOCK_TOKENS = 256
_SLOT_BYTES = 1 << 20
#: decode rows a grid step walks (the largest divisor of B up to this)
_ROWS_PER_STEP = 8


def pages_per_block(page_size: int, feat: int, itemsize: int) -> int:
    """``nb``: pages the kernel fetches and attends at a time, from the
    page geometry alone (``feat`` = KVH*D)."""
    return max(1, min(_BLOCK_TOKENS // page_size,
                      _SLOT_BYTES // (page_size * feat * itemsize)))


def n_blocks(lengths, page_size: int, nb: int):
    """Blocks the kernel's loop walks for rows of ``lengths`` visible
    tokens (0 = inactive).  The kernel calls it on a row's scalar, the
    engine on the step's numpy lengths (``decode_kv_blocks``)."""
    return (lengths + (nb * page_size - 1)) // (nb * page_size)


def _decode_kernel(pt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, *rest,
                   ps, nb, scale, kvh, quant, alibi):
    """One grid step: ``rows`` decode rows, walked as ONE sequence of
    (row, block) items over the live pages of the rows that have any, so
    the copies of the next item — the same row's next block or the next
    active row's first — are in flight while this one is attended."""
    rest = list(rest)
    sl_ref = rest.pop(0) if alibi else None
    ks_hbm, vs_hbm = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    (o_ref, m_scr, l_scr, acc_scr, s_scr, p_scr, pv_scr, k_buf, v_buf, sems,
     next_row) = rest[:11]
    ks_buf, vs_buf = rest[11:] if quant else (None, None)
    rows, nh, d = q_ref.shape
    g = nh // kvh
    base = pl.program_id(0) * rows
    T = nb * ps
    layer = layer_ref[0]
    # (page -> source, ring, semaphore column); the scales, one layer's
    # already, ride their codes' semaphore
    streams = [(lambda page: k_hbm.at[layer, page], k_buf, 0),
               (lambda page: v_hbm.at[layer, page], v_buf, 1)]
    if quant:
        streams += [(lambda page: ks_hbm.at[page], ks_buf, 0),
                    (lambda page: vs_hbm.at[page], vs_buf, 1)]

    def length(r):
        return jnp.minimum(len_ref[base + r], pt_ref.shape[1] * ps)

    # next_row[r]: the first row after r that has pages (``rows`` if none);
    # ``row``: the first that has any; ``total``: the items of this step
    row, total = jnp.int32(rows), jnp.int32(0)
    for r in reversed(range(rows)):
        next_row[r] = row
        row = jnp.where(length(r) > 0, r, row)
        total = total + n_blocks(length(r), ps, nb)

    def block_dma(r, i, slot, wait):
        """Start (or wait for) the copies of the live pages of row ``r``'s
        block ``i`` into ring slot ``slot``."""
        n_live = jnp.minimum((length(r) + ps - 1) // ps - i * nb, nb)

        def copy(j, _):
            page = pt_ref[base + r, i * nb + j]
            for src, buf, s in streams:
                dma = pltpu.make_async_copy(
                    src(page), buf.at[slot, j], sems.at[slot, s])
                dma.wait() if wait else dma.start()
            return 0

        def zero(j, _):
            # a page never fetched holds whatever the slot held: its
            # scores are masked, but 0 * NaN is NaN in p @ v
            v_buf[slot, j] = jnp.zeros(v_buf.shape[2:], v_buf.dtype)
            if quant:
                vs_buf[slot, j] = jnp.zeros(vs_buf.shape[2:], vs_buf.dtype)
            return 0

        jax.lax.fori_loop(0, n_live, copy, 0)
        if wait:
            jax.lax.fori_loop(n_live, nb, zero, 0)

    # a row that has no pages is never visited
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        block_dma(row, 0, 0, wait=False)

    def item(n, carry):
        r, i = carry
        slot = jax.lax.rem(n, 2)
        seq_len = length(r)
        last = (i + 1) * T >= seq_len
        r_next = jnp.where(last, next_row[r], r)
        i_next = jnp.where(last, 0, i + 1)

        @pl.when(n + 1 < total)
        def _():
            block_dma(r_next, i_next, 1 - slot, wait=False)

        block_dma(r, i, slot, wait=True)

        @pl.when(i == 0)
        def _():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        def head(buf, scales, h):
            """K or V head ``h`` of the block, [T, D] as the queries are."""
            x = buf[slot, :, :, h * d:(h + 1) * d].reshape(T, d)
            if quant:  # int8 codes * per-(slot, head) scale, in VMEM
                x = (x.astype(jnp.float32) * scales[
                    slot, :, :, h:h + 1].reshape(T, 1)).astype(q_ref.dtype)
            return x

        # 1. every head's scores, the products back to back: [NH, T]
        for h in range(kvh):
            s_scr[h * g:(h + 1) * g] = jax.lax.dot_general(
                q_ref[r, h * g:(h + 1) * g], head(k_buf, ks_buf, h),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        # 2. one online-softmax update on the stacked scores
        slots = i * T + jax.lax.broadcasted_iota(jnp.int32, (nh, T), 1)
        s = s_scr[...] * scale
        if alibi:
            # ALiBi distance penalty from page-slot indices (bloom)
            s = s - sl_ref[...] * (seq_len - 1 - slots).astype(jnp.float32)
        s = jnp.where(slots < seq_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        p_scr[...] = p
        # 3. every head's values, back to back: [NH, D], and one accumulate
        for h in range(kvh):
            v = head(v_buf, vs_buf, h)
            pv_scr[h * g:(h + 1) * g] = jnp.dot(
                p_scr[h * g:(h + 1) * g].astype(v.dtype), v,
                preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * alpha + pv_scr[...]

        @pl.when(last)
        def _():
            o_ref[r] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

        return r_next, i_next

    jax.lax.fori_loop(0, total, item, (row, jnp.int32(0)))


def paged_decode_attention(q, k_pool, v_pool, page_table, positions,
                           k_scale=None, v_scale=None, alibi_slopes=None,
                           layer=None, active=None, scale=None,
                           name="dstpu_paged_decode"):
    """q: [B, NH, D]; pools: the engine's ``[L, P, ps, KVH*D]`` read at
    int32 scalar ``layer`` (int8 codes when ``k_scale``/``v_scale``
    ``[L, P, ps, KVH]`` given), or with ``layer=None`` one layer's
    ``[P, ps, KVH, D]`` (scales ``[P, ps, KVH]``); page_table: [B, MP]
    int32; positions: [B] int32; ``alibi_slopes``: optional [NH] per-head
    ALiBi slopes (bias built in-kernel from slot indices); ``active``:
    optional [B] bool — a row that is not active attends nothing and
    returns zeros; ``scale``: the scores' factor where it is not
    ``1 / sqrt(D)``; ``name``: the kernel's name in a device trace, for a
    caller whose "pages" are another cache (a window layer's ring in the
    state slots is ``dstpu_window_decode``).  Returns [B, NH, D]."""
    if layer is None:
        # one layer's pool: merging KVH and D relayouts it, which is only
        # acceptable because nothing on the serving path comes this way
        P, ps = k_pool.shape[:2]
        k_pool, v_pool = (x.reshape(1, P, ps, -1) for x in (k_pool, v_pool))
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    lengths = positions.astype(jnp.int32) + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    if alibi_slopes is not None:
        alibi_slopes = jnp.asarray(alibi_slopes, jnp.float32).reshape(-1, 1)
    return _decode_call(
        q, k_pool, v_pool, page_table, lengths,
        jnp.asarray(layer, jnp.int32).reshape(1), k_scale, v_scale,
        alibi_slopes, scale=scale or 1.0 / math.sqrt(q.shape[-1]), name=name,
        interpret=pallas_interpret())


# a jit of its own: a program that reaches the kernel once a layer of an
# unrolled stack traces the body once a signature and not once a layer
# (PERF.md, PR 35: set-up)
@functools.partial(jax.jit, static_argnames=("scale", "name", "interpret"))
def _decode_call(q, k_pool, v_pool, page_table, lengths, layer, k_scale,
                 v_scale, alibi_slopes, *, scale, name, interpret):
    B, NH, D = q.shape
    ps, F = k_pool.shape[2:]
    KVH = F // D
    assert KVH * D == F and NH % KVH == 0
    quant = k_scale is not None
    alibi = alibi_slopes is not None
    nb = pages_per_block(ps, F, k_pool.dtype.itemsize)
    rows = max(r for r in range(1, _ROWS_PER_STEP + 1) if B % r == 0)

    q_spec = pl.BlockSpec((rows, NH, D), lambda b, *_: (b, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, hbm, hbm]
    args = [q, k_pool, v_pool]
    scratch = [
        pltpu.VMEM((NH, 1), jnp.float32),        # running maximum
        pltpu.VMEM((NH, 1), jnp.float32),        # running sum
        pltpu.VMEM((NH, D), jnp.float32),        # accumulator
        pltpu.VMEM((NH, nb * ps), jnp.float32),  # a block's scores
        pltpu.VMEM((NH, nb * ps), jnp.float32),  # its probabilities
        pltpu.VMEM((NH, D), jnp.float32),        # its values' products
        pltpu.VMEM((2, nb, ps, F), k_pool.dtype),
        pltpu.VMEM((2, nb, ps, F), v_pool.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.SMEM((rows,), jnp.int32),
    ]
    if alibi:
        # rides right after k/v so the kernel pops it off *rest first
        in_specs.append(pl.BlockSpec((NH, 1), lambda b, *_: (0, 0)))
        args.append(alibi_slopes)
    if quant:
        # Mosaic cannot slice a page out of an HBM operand whose minor
        # dimension (KVH) is under a lane tile, so the scales come as this
        # layer's alone, padded to whole lanes: 1/L of what XLA's relayout
        # of the unpadded operand moved (PERF.md, int8 scales)
        lanes = KVH + -KVH % 128
        scales = [jnp.pad(jax.lax.dynamic_index_in_dim(s, layer[0], 0, False),
                          ((0, 0), (0, 0), (0, lanes - KVH)))
                  for s in (k_scale, v_scale)]
        in_specs += [hbm, hbm]
        args += scales
        scratch += [pltpu.VMEM((2, nb, ps, lanes), s.dtype) for s in scales]

    kernel = pl.pallas_call(
        functools.partial(_decode_kernel, ps=ps, nb=nb, scale=scale, kvh=KVH,
                          quant=quant, alibi=alibi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // rows,),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((B, NH, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )
    return kernel(page_table, lengths, layer, *args)
