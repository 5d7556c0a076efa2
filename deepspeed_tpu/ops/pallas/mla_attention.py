"""Latent-attention decode (Pallas TPU kernel): one query token a sequence
attends that sequence's *latent pages* in place.

A latent-attention (MLA) layer caches one row a token, ``[c | k_rope]``: the
normalised latent ``c`` (``R`` wide) and the rotary key all heads share
(``dr`` wide).  With the key and value up-projections absorbed into the query
and the output (``model_runner``: ``q~ = q_nope W_uk^T``, ``o = u W_uv``),
every head is a query ``[q~ | q_rope]`` of ``R + dr`` against ONE shared key,
the cached row itself, and its value is the row's first ``R`` lanes.  So a
page is fetched **once** and serves as keys and as values — a second fetch as
V would double the bytes of a kernel the bytes bound.

The walk is ``paged_attention.py``'s (its ``pages_per_block`` / ``n_blocks``
are used as they are): a grid step a few decode rows, walked as ONE sequence
of (row, block) items over the pages the rows *have* — none for an inactive
row, which is never visited, reads nothing and returns zeros; each item's live
pages copied HBM -> VMEM one a page into a two-slot ring, the next item's
copies started before this one's are waited for; pages of a row's last block
past its length are not fetched (their rows are zeroed: they are values too,
and 0 * NaN is NaN).  A block is attended in three phases: the score products
``[NH, R] x [R, T]`` and ``[NH, dr] x [dr, T]`` into one ``[NH, T]`` float32
tile, one online-softmax update, one value product ``[NH, T] x [T, R]``.

Layout: q ``[B, NH, R + dr]`` (any softmax scale already multiplied in);
pool ``[L, P, ps, F]`` as the engine stores it, ``F >= R + dr`` a whole number
of lane tiles (``layer_types.latent_width``: Mosaic cannot slice a page out
of an HBM operand whose minor dimension is not, and the device pads a row of
320 to 384 lanes whatever it is called; lanes past ``R + dr`` are never read
into a product); page_table ``[B, MP]``
int32; positions ``[B]`` int32 (slot of the CURRENT token); ``active`` ``[B]``
bool.  Returns ``[B, NH, R]``: each head's softmax-weighted sum of latents.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret
from .paged_attention import (_ROWS_PER_STEP, NEG_INF, n_blocks,
                              pages_per_block)


def _mla_kernel(pt_ref, len_ref, layer_ref, q_ref, c_hbm, o_ref, m_scr, l_scr,
                acc_scr, p_scr, c_buf, sems, next_row, *, ps, nb, rank):
    rows, nh, qw = q_ref.shape
    base = pl.program_id(0) * rows
    T = nb * ps
    layer = layer_ref[0]

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
        n_live = jnp.minimum((length(r) + ps - 1) // ps - i * nb, nb)

        def copy(j, _):
            dma = pltpu.make_async_copy(
                c_hbm.at[layer, pt_ref[base + r, i * nb + j]],
                c_buf.at[slot, j], sems.at[slot])
            dma.wait() if wait else dma.start()
            return 0

        def zero(j, _):
            c_buf[slot, j] = jnp.zeros(c_buf.shape[2:], c_buf.dtype)
            return 0

        jax.lax.fori_loop(0, n_live, copy, 0)
        if wait:
            jax.lax.fori_loop(n_live, nb, zero, 0)

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

        c = c_buf[slot, :, :, :rank].reshape(T, rank)    # keys and values
        kr = c_buf[slot, :, :, rank:qw].reshape(T, -1)   # the rotary key
        dims = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(q_ref[r, :, :rank], c, dims,
                                preferred_element_type=jnp.float32) \
            + jax.lax.dot_general(q_ref[r, :, rank:], kr, dims,
                                  preferred_element_type=jnp.float32)
        slots = i * T + jax.lax.broadcasted_iota(jnp.int32, (nh, T), 1)
        s = jnp.where(slots < seq_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        m_scr[...] = m_new
        p_scr[...] = p
        acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
            p_scr[...].astype(c.dtype), c, preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[r] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

        return r_next, i_next

    jax.lax.fori_loop(0, total, item, (row, jnp.int32(0)))


def mla_decode_attention(q, pool, page_table, positions, layer, active, rank):
    """q ``[B, NH, rank + dr]``; pool ``[L, P, ps, F]`` read at int32
    scalar ``layer``; a row that is not ``active`` attends nothing and
    returns zeros.  Returns ``[B, NH, rank]``."""
    lengths = jnp.where(active, positions.astype(jnp.int32) + 1, 0)
    return _mla_call(q, pool, page_table, lengths,
                     jnp.asarray(layer, jnp.int32).reshape(1), rank=rank,
                     interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("rank", "interpret"))
def _mla_call(q, pool, page_table, lengths, layer, *, rank, interpret):
    B, NH, QW = q.shape
    ps, F = pool.shape[2:]
    assert 0 < rank < QW <= F
    nb = pages_per_block(ps, F, pool.dtype.itemsize)
    rows = max(r for r in range(1, _ROWS_PER_STEP + 1) if B % r == 0)
    kernel = pl.pallas_call(
        functools.partial(_mla_kernel, ps=ps, nb=nb, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B // rows,),
            in_specs=[pl.BlockSpec((rows, NH, QW), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, NH, rank), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((NH, 1), jnp.float32),        # running maximum
                pltpu.VMEM((NH, 1), jnp.float32),        # running sum
                pltpu.VMEM((NH, rank), jnp.float32),     # accumulator
                pltpu.VMEM((NH, nb * ps), jnp.float32),  # probabilities
                pltpu.VMEM((2, nb, ps, F), pool.dtype),  # the two-slot ring
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((rows,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, NH, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="dstpu_mla_decode",
    )
    return kernel(page_table, lengths, layer, q, pool)
