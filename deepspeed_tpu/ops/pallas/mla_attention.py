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

The walk has ``paged_attention.py``'s shape (its ``n_blocks`` is used as it
is): a grid step a few decode rows, walked as ONE sequence of (row, block)
items over the pages the rows *have* — none for an inactive row, which is
never visited, reads nothing and returns zeros; each item's live pages copied
HBM -> VMEM one a page into a two-slot ring, the next item's copies started
before this one's are waited for.  What an item holds and what a page of it
costs follow the latent page's BYTES, not a count of tokens: a latent page is
12 - 20 KB where a K/V page is 32 - 64, and at the paged kernel's 256 tokens
an item's copies were a fifth of a slot while its starts, its waits and its
chain of dependent products were paid whole, one after the other in one
instruction stream — the same 75 - 80 ns a page at rank 256 and at rank 512
(PERF.md section 6, PR 59: the knock-outs and the bundle counts).  So:

- an item fills a slot of ``_SLOT_BYTES`` under ``_BLOCK_TOKENS``
  (``latent_pages_per_block``: 64 pages at 384 lanes, 48 at 640), and the
  chain of products is paid once for four times the tokens;
- a full item is waited for ONCE, by one descriptor over the whole slot (a
  DMA semaphore counts bytes, so the wait of what ``nb`` page copies signal
  is the slot's size); only a row's last, partial item waits a page at a
  time, and its pages past the row's length are not fetched (their rows are
  zeroed: they are values too, and 0 * NaN is NaN);
- a start is a chain of scalar operations — the table entry, the two
  addresses — so a full item's are issued ``_START_UNROLL`` to a loop trip
  (another page's chain is all that can fill the bundles), the page table is
  handed over flat (an entry's address is one add), and the compiler's
  checks of each copy's two ends (16 of a start's 45 operations) are off,
  the entry held inside the pool by a clamp instead.

A block is attended in three phases: the score products ``[NH, R] x [R, T]``
and ``[NH, dr] x [dr, T]`` into one ``[NH, T]`` float32 tile, one
online-softmax update, one value product ``[NH, T] x [T, R]``.

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
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret
from .paged_attention import _ROWS_PER_STEP, NEG_INF, n_blocks

#: the VMEM one slot of the ring may take, and the most tokens of a block:
#: the ``[NH, T]`` float32 score tile and the probabilities' scratch grow
#: with it, and so do the masked positions of a row's last block
#: (``latent_block_slots``)
_SLOT_BYTES = 1 << 20
_BLOCK_TOKENS = 1024
_LANES = 128
#: page copies started a trip of a full item's loop
_START_UNROLL = 8


def latent_pages_per_block(page_size: int, lanes: int, itemsize: int) -> int:
    """``nb``: latent pages the kernel fetches and attends at a time, from
    the pool's geometry alone — as many as fill a slot, under the cap on
    tokens, a whole number of the score tile's lane tiles."""
    nb = max(1, min(_SLOT_BYTES // (page_size * lanes * itemsize),
                    _BLOCK_TOKENS // page_size))
    tile = max(1, _LANES // page_size)
    return nb - nb % tile if nb > tile else nb


def _mla_kernel(pt_ref, len_ref, layer_ref, q_ref, c_hbm, o_ref, m_scr, l_scr,
                acc_scr, p_scr, c_buf, sems, next_row, *, ps, nb, rank, mp):
    rows, nh, qw = q_ref.shape
    base = pl.program_id(0) * rows
    T = nb * ps
    layer = layer_ref[0]
    unroll = math.gcd(nb, _START_UNROLL)

    def length(r):
        return jnp.minimum(len_ref[base + r], mp * ps)

    # next_row[r]: the first row after r that has pages (``rows`` if none);
    # ``row``: the first that has any; ``total``: the items of this step
    row, total = jnp.int32(rows), jnp.int32(0)
    for r in reversed(range(rows)):
        next_row[r] = row
        row = jnp.where(length(r) > 0, r, row)
        total = total + n_blocks(length(r), ps, nb)

    def live_pages(r, i):
        return jnp.minimum((length(r) + ps - 1) // ps - i * nb, nb)

    def page_copy(first, slot, j):
        """Page ``j`` of the item whose table entries start at ``first``
        (the table is flat: one add to an entry's address).  The entry is
        held inside the pool here, in two operations, because the
        compiler's own checks of a copy's two ends — sixteen of a start's
        forty-five — are off (``disable_bounds_checks``)."""
        page = jnp.clip(pt_ref[first + j], 0, c_hbm.shape[1] - 1)
        return pltpu.make_async_copy(c_hbm.at[layer, page],
                                     c_buf.at[slot, j], sems.at[slot])

    def start_item(r, i, slot):
        n_live = live_pages(r, i)
        first = (base + r) * mp + i * nb

        # a full item's starts _START_UNROLL at a time: a start is a chain of
        # scalar operations (the entry, two addresses) that only another
        # page's chain can fill the bundles of
        @pl.when(n_live == nb)
        def _():
            def group(g, _):
                for k in range(unroll):
                    page_copy(first, slot, g * unroll + k).start()
                return 0
            jax.lax.fori_loop(0, nb // unroll, group, 0)

        @pl.when(n_live < nb)
        def _():
            def start(j, _):
                page_copy(first, slot, j).start()
                return 0

            jax.lax.fori_loop(0, n_live, start, 0)

    def wait_item(r, i, slot):
        """What an item started it waits for, byte for byte, on its own
        slot's semaphore: a full one in ONE wait of the slot's size."""
        n_live = live_pages(r, i)

        @pl.when(n_live == nb)
        def _():
            pltpu.make_async_copy(c_hbm.at[layer, pl.ds(0, nb)],
                                  c_buf.at[slot], sems.at[slot]).wait()

        @pl.when(n_live < nb)
        def _():
            def zero(j, _):
                c_buf[slot, j] = jnp.zeros(c_buf.shape[2:], c_buf.dtype)
                return 0

            def wait(j, _):
                pltpu.make_async_copy(c_hbm.at[layer, 0], c_buf.at[slot, j],
                                      sems.at[slot]).wait()
                return 0

            jax.lax.fori_loop(0, n_live, wait, 0)
            jax.lax.fori_loop(n_live, nb, zero, 0)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        start_item(row, 0, 0)

    def item(n, carry):
        r, i = carry
        slot = jax.lax.rem(n, 2)
        seq_len = length(r)
        last = (i + 1) * T >= seq_len
        r_next = jnp.where(last, next_row[r], r)
        i_next = jnp.where(last, 0, i + 1)

        @pl.when(n + 1 < total)
        def _():
            start_item(r_next, i_next, 1 - slot)

        wait_item(r, i, slot)

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
    nb = latent_pages_per_block(*pool.shape[2:], pool.dtype.itemsize)
    return _mla_call(q, pool, page_table, lengths,
                     jnp.asarray(layer, jnp.int32).reshape(1), rank=rank,
                     nb=nb, interpret=pallas_interpret())


@functools.partial(jax.jit, static_argnames=("rank", "nb", "interpret"))
def _mla_call(q, pool, page_table, lengths, layer, *, rank, nb, interpret):
    B, NH, QW = q.shape
    ps, F = pool.shape[2:]
    assert 0 < rank < QW <= F
    mp = page_table.shape[1]
    rows = max(r for r in range(1, _ROWS_PER_STEP + 1) if B % r == 0)
    kernel = pl.pallas_call(
        functools.partial(_mla_kernel, ps=ps, nb=nb, rank=rank, mp=mp),
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
            dimension_semantics=("parallel",), disable_bounds_checks=True),
        interpret=interpret,
        name="dstpu_mla_decode",
    )
    return kernel(page_table.reshape(-1), lengths, layer, q, pool)
