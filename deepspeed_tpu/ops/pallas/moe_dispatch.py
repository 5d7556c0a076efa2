"""Dispatch and combine around the grouped expert matmul — Pallas TPU.

The dropless expert layer (``moe/sharded_moe.py::_sorted_expert_ffn``) lays
each pick's token row into a buffer sorted and padded by expert, runs the
grouped matmuls over it and adds each pick's output row, times its gate,
back onto its token.  Both moves are row gathers whose work follows the
picks *held here* — the blocks that hold picks, ``n_real`` of
``sort_pad_by_expert`` — and not the ``T * top_k`` picks routed or the
worst-case buffer:

- ``dstpu_moe_dispatch``: ``xs[r] = src[row_src[r]]`` for the rows of the
  first ``n_real`` blocks, a grid step a block; a block's first ``n_valid``
  rows are fetched HBM → VMEM by one async copy a row into a two-slot ring
  (the next block's copies are in flight while this one is written out),
  its other rows are written as zeros, and blocks past ``n_real`` are not
  written at all (the grouped matmul never reads them);
- ``dstpu_moe_combine``: ``out[t] = sum_k w[t, k] * ys[dest[t, k]]`` over the
  picks with ``dest >= 0``, a grid step a block of tokens, summed in float32
  and written once a token (zeros where no pick is held); its ``dot`` form
  returns ``<ys[dest[t, k]], d[t]>`` per pick instead.

Each is the other's transpose, so the backward needs no third algorithm
(``moe_dispatch`` / ``moe_combine`` below).

A row is fetched whole, as tiles: Mosaic cannot slice one row out of a
``[T, H]`` operand in HBM (its second-minor dimension is tiled by 8 or 16
rows), so the source is handed over as ``[T, H / 128, 128]`` — a token is
then tiles of its own, indexed on an untiled dimension — and the kernel
reads lane tile ``c`` of every fetched row with one sublane-strided load.
A bfloat16 source is read through its uint32 view (two of a token's lane
tiles a word, low half first), because a packed row is half a sublane;
float32 and bfloat16 are the dtypes served.

A fetched row is ``Sw`` sublanes of 32-bit words (``_row_tiles``) at
``j * Sw`` of the ring, whatever ``Sw`` is: a VMEM buffer of words 128
lanes wide is linear, a sublane after a sublane, so a copy lands at any of
them and the loads take any stride.  Nothing needs a row to be whole
8-sublane tiles: on a v5e both kernels read equal to XLA at 1, 2, 4, 8, 12
(a bfloat16 row of 3072) and 24 word-sublanes (``PERF.md`` section 6, PR 57).
What the SOURCE needs is another matter: the uint32 view of a bfloat16
operand is tiled by 4 word-sublanes, and Mosaic refuses to cut out a row of
3, 5, 6, 10 or 14 of them (a bfloat16 row of 3584: Xing4.0), which
``rows_kernel_serves`` therefore leaves to XLA's scatter and gathers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.activation_checkpointing.checkpointing import MOE_DISPATCH_ROWS
from ...utils.platform import on_tpu, pallas_interpret

_LANES = 128
#: VMEM one slot of the combine's ring may take (two slots are in flight)
_COMBINE_SLOT_BYTES = 4 * 2 ** 20
#: picks a call may route: both kernels walk a map of them in SMEM (compiled
#: for a v5e, PR 33: 131,072 fit, 262,144 run out of its 1 MiB)
_MAX_PICKS = 2 ** 17


def _row_tiles(h: int, dtype):
    """``(S, L, Sw)``: a row of ``h`` as ``S`` lane tiles of ``L``, held in
    ``Sw`` sublanes of 32-bit words."""
    lanes = min(h, _LANES)
    s = h // lanes
    return s, lanes, s // (4 // jnp.dtype(dtype).itemsize)


def rows_kernel_serves(h: int, dtype, picks: int) -> bool:
    """Whether the two kernels take a call of ``picks`` picks over rows of
    ``h`` of ``dtype``: float32 or bfloat16 in whole lane tiles, an even
    number of them where two share a word, on the TPU tiles of all 128
    lanes and a packed row of 1, 2 or a multiple of 4 word-sublanes, and a
    pick map that fits the scalar memory."""
    dtype = jnp.dtype(dtype)
    if picks > _MAX_PICKS or dtype not in (jnp.dtype(jnp.float32),
                                           jnp.dtype(jnp.bfloat16)):
        return False
    s, lanes, sw = _row_tiles(h, dtype)
    if s * lanes != h or sw * (4 // dtype.itemsize) != s:
        return False
    if not on_tpu():
        return True
    # a bfloat16 source is read through its uint32 view, which the device
    # tiles by 4 word-sublanes: Mosaic cuts a token's row out of it only
    # where the row is whole tiles or smaller than one ("Slice shape along
    # dimension 1 must be aligned to tiling (4), but is 14": 3, 5, 6, 10 and
    # 14 are refused, 1, 2, 4, 8, 12, 16 and 24 compile; a float32 row
    # compiles at every count from 1 to 14: PERF.md section 6, PR 58)
    return lanes == _LANES and (dtype.itemsize == 4 or sw <= 2
                                or sw % 4 == 0)


def _as_tiles(x):
    s, lanes, _ = _row_tiles(x.shape[1], x.dtype)
    return x.reshape(x.shape[0], s, lanes)


def _halves(words, packed: bool):
    """The lane tiles a tile of fetched words holds, in order, as float32
    (a bfloat16 is the high half of a float32; all-zero words are zeros)."""
    if not packed:
        return [words]
    return [pltpu.bitcast(words << 16, jnp.float32),
            pltpu.bitcast(words & jnp.uint32(0xFFFF0000), jnp.float32)]


def _column(ref, k: int):
    """``[bt, k]`` as ``[k * bt, 1]``, column after column: the order of the
    combine's ring."""
    x = ref[...]
    return jnp.concatenate([x[:, kk:kk + 1] for kk in range(k)], axis=0)


def _dispatch_kernel(nr_ref, nv_ref, src_ref, x_hbm, o_ref, buf, sem, *,
                     bs, sw, packed):
    i, nr = pl.program_id(0), nr_ref[0]
    lanes = buf.shape[-1]
    src = x_hbm.bitcast(jnp.uint32) if packed else x_hbm

    def copies(b, slot, wait):
        def one(j, c):
            dma = pltpu.make_async_copy(
                src.at[src_ref[b * bs + j]],
                buf.at[slot, pl.ds(pl.multiple_of(j * sw, sw), sw)],
                sem.at[slot])
            dma.wait() if wait else dma.start()
            return c
        jax.lax.fori_loop(0, nv_ref[b], one, 0)

    @pl.when(jnp.logical_and(i == 0, nr > 0))
    def _():
        copies(0, 0, False)

    @pl.when(i < nr)
    def _():
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < nr)
        def _():
            copies(i + 1, 1 - slot, False)

        copies(i, slot, True)
        # a row past the block's picks was not fetched: the slot holds
        # whatever it held, and the grouped matmul's dW sums every row of
        # a real block
        valid = jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0) < nv_ref[i]
        halves = 2 if packed else 1
        for w in range(sw):  # word-sublane w of every fetched row
            words = buf[slot, pl.ds(w, bs, stride=sw), :]
            words = jnp.where(valid, words, jnp.zeros_like(words))
            for c, tile in enumerate(_halves(words, packed), halves * w):
                o_ref[:, c * lanes:(c + 1) * lanes] = tile.astype(o_ref.dtype)


def _last_real(i, nr, *_):  # blocks past the real ones: nothing written
    return jnp.minimum(i, jnp.maximum(nr[0] - 1, 0)), 0


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _dispatch_call(src, row_src, n_valid, n_real, *, block_rows, interpret):
    tiles = src if src.ndim == 3 else _as_tiles(src)
    h, dtype = tiles.shape[1] * tiles.shape[2], src.dtype
    s, lanes, sw = _row_tiles(h, dtype)
    packed = dtype.itemsize == 2
    n_blocks = n_valid.shape[0]
    word = jnp.uint32 if packed else dtype
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, bs=block_rows, sw=sw,
                          packed=packed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_rows, h), _last_real),
            scratch_shapes=[pltpu.VMEM((2, block_rows * sw, lanes), word),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n_blocks * block_rows, h), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * block_rows * h * (4 + dtype.itemsize)
            + 16 * 2 ** 20),
        interpret=interpret,
        name="dstpu_moe_dispatch",
    )(jnp.asarray(n_real, jnp.int32).reshape(1), n_valid.astype(jnp.int32),
      row_src.astype(jnp.int32), tiles)


def dispatch_rows(src, row_src, n_valid, n_real, block_rows: int):
    """``[n_blocks * block_rows, H]``: row ``r`` of block ``b < n_real`` is
    ``src[row_src[r]]`` while ``r - b * block_rows < n_valid[b]`` and zeros
    past that; the rows of the blocks from ``n_real`` on are NOT WRITTEN.
    ``src`` is ``[N, H]``, or ``[N, H / 128, 128]`` where the caller can
    make its rows as lane tiles at no cost.
    A jitted call: a program that runs the layer several times traces and
    lowers the kernel once (a trace is ~0.3 s of Python, and a serving
    engine warms seven programs of four layer calls each)."""
    return _dispatch_call(src, row_src, n_valid, n_real,
                          block_rows=block_rows,
                          interpret=pallas_interpret())


def _combine_kernel(cnt_ref, dest_ref, y_hbm, dv_ref, second_ref, o_ref, buf,
                    sem, *, bt, k, sw, packed, dot):
    i, n = pl.program_id(0), pl.num_programs(0)
    lanes = buf.shape[-1]
    src = y_hbm.bitcast(jnp.uint32) if packed else y_hbm

    def ring(slot, kk, j):  # pick kk of the block's token j
        return buf.at[slot, pl.ds(pl.multiple_of((kk * bt + j) * sw, sw), sw)]

    def start(blk, slot):
        def token(j, c):
            for kk in range(k):
                row = dest_ref[(blk * bt + j) * k + kk]

                @pl.when(row >= 0)
                def _():
                    pltpu.make_async_copy(src.at[row], ring(slot, kk, j),
                                          sem.at[slot]).start()
            return c

        @pl.when(cnt_ref[blk] > 0)
        def _():
            jax.lax.fori_loop(0, bt, token, 0)

    def wait(blk, slot):  # every copy is one row: wait for as many
        def one(_, c):
            pltpu.make_async_copy(src.at[0], ring(slot, 0, 0),
                                  sem.at[slot]).wait()
            return c
        jax.lax.fori_loop(0, cnt_ref[blk], one, 0)

    @pl.when(i == 0)
    def _():
        start(0, 0)

    slot = jax.lax.rem(i, 2)

    @pl.when(i + 1 < n)
    def _():
        start(i + 1, 1 - slot)

    wait(i, slot)
    # a pick that is not held was not fetched: its place in the slot holds
    # whatever it held.  One strided load reads a word-sublane of every
    # (pick, token) of the block at once, so the traced body is a few
    # operations a lane tile whatever k is (tracing is Python time that no
    # compile cache keeps: seconds a program at k 8 when it was a load a
    # pick)
    held = _column(dv_ref, k) >= 0
    halves = 2 if packed else 1
    if dot:
        acc = jnp.zeros((k, bt, lanes), jnp.float32)
    else:
        weight = _column(second_ref, k)
    for w in range(sw):
        words = buf[slot, pl.ds(w, k * bt, stride=sw), :]
        words = jnp.where(held, words, jnp.zeros_like(words))
        for c, tile in enumerate(_halves(words, packed), halves * w):
            at = slice(c * lanes, (c + 1) * lanes)
            if dot:
                acc += tile.reshape(k, bt, lanes) \
                    * second_ref[:, at].astype(jnp.float32)[None]
            else:
                o_ref[:, at] = jnp.sum(
                    (tile * weight).reshape(k, bt, lanes),
                    axis=0).astype(o_ref.dtype)
    if dot:
        for kk in range(k):
            o_ref[:, kk:kk + 1] = jnp.sum(acc[kk], axis=1, keepdims=True)


def _token_block(t: int, k: int, h: int, dtype) -> int:
    """Tokens a grid step of the combine takes: a power of two from the
    dtype's sublane tile to 128, its ``k`` rows a token within the slot."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)
    fits = _COMBINE_SLOT_BYTES // (k * h * itemsize)
    want = min(128, max(sublane, 1 << max(fits, 1).bit_length() - 1))
    while want > sublane and want // 2 >= t:
        want //= 2
    return want


@functools.partial(jax.jit, static_argnames=("dot", "interpret"))
def _combine_call(ys, dest, second, *, dot, interpret):
    (t, k), h, dtype = dest.shape, ys.shape[1], ys.dtype
    s, lanes, sw = _row_tiles(h, dtype)
    packed = dtype.itemsize == 2
    bt = _token_block(t, k, h, dtype)
    tp = -(-t // bt) * bt
    dest = jnp.pad(dest.astype(jnp.int32), ((0, tp - t), (0, 0)),
                   constant_values=-1)
    second = jnp.pad(second, ((0, tp - t), (0, 0)))
    width, out_dtype = (k, jnp.float32) if dot else (h, dtype)
    counts = jnp.sum((dest >= 0).reshape(tp // bt, bt * k), axis=1,
                     dtype=jnp.int32)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, bt=bt, k=k, sw=sw, packed=packed,
                          dot=dot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tp // bt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((bt, k), lambda i, *_: (i, 0)),
                      pl.BlockSpec((bt, second.shape[1]),
                                   lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec((bt, width), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k * bt * sw, lanes),
                           jnp.uint32 if packed else dtype),
                pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct((tp, width), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * k * bt * h * 4 + 8 * bt * h * 4
            + 16 * 2 ** 20),
        interpret=interpret,
        name="dstpu_moe_combine",
    )(counts, dest.reshape(tp * k), _as_tiles(ys), dest, second)
    return out[:t]


def combine_rows(ys, dest, weights=None, dot=None):
    """``dest`` ``[T, K]`` int32: the row of ``ys`` each pick's output is in,
    negative where the pick is not held here.  With ``weights`` ``[T, K]``:
    ``out[t] = sum_k weights[t, k] * ys[dest[t, k]]`` over the held picks,
    summed in float32, ``[T, H]`` of ``ys``'s dtype.  With ``dot`` ``[T, H]``:
    ``<ys[dest[t, k]], dot[t]>`` per pick in float32 ``[T, K]``, 0 where the
    pick is not held.  No row that ``dest`` does not name is read.  A jitted
    call, as ``dispatch_rows``."""
    second = weights.astype(jnp.float32) if dot is None else dot
    return _combine_call(ys, dest, second, dot=dot is not None,
                         interpret=pallas_interpret())


# ---------------------------------------------------------------- the pair
# ``row_pick`` [n_rows]: the pick (token * top_k + k) each buffer row holds;
# ``n_valid`` [n_blocks]: the rows of each block that hold one (0 from
# ``n_real`` on); ``dest`` [T, top_k]: each pick's row, negative where the
# pick is not held — ``moe/sharded_moe.py::pick_row_maps`` builds the three


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def moe_dispatch(xt, row_pick, n_valid, n_real, dest, block_rows: int):
    """``xs``: each held pick's token row in its buffer row, zeros in the
    other rows of the blocks that hold picks; differentiates into a combine
    of ``d xs`` with unit weights."""
    return dispatch_rows(xt, row_pick // dest.shape[1], n_valid, n_real,
                         block_rows)


def _moe_dispatch_fwd(xt, row_pick, n_valid, n_real, dest, block_rows):
    xs = dispatch_rows(xt, row_pick // dest.shape[1], n_valid, n_real,
                       block_rows)
    return checkpoint_name(xs, MOE_DISPATCH_ROWS), dest


def _moe_dispatch_bwd(block_rows, dest, dxs):
    dxt = combine_rows(dxs, dest, weights=jnp.ones(dest.shape, jnp.float32))
    return dxt, None, None, None, None


moe_dispatch.defvjp(_moe_dispatch_fwd, _moe_dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def moe_combine(ys, gate, row_pick, n_valid, n_real, dest, block_rows: int):
    """``out[t] = sum_k gate[t, k] * ys[dest[t, k]]`` over the held picks
    (``gate`` ``[T, top_k]``).  ``d ys`` is a dispatch of ``gate * d out`` —
    zeros in the padding rows of the real blocks, which the grouped
    matmul's dW sums — and ``d gate[t, k] = <ys[dest[t, k]], d out[t]>``."""
    return combine_rows(ys, dest, weights=gate)


def _moe_combine_fwd(ys, gate, row_pick, n_valid, n_real, dest, block_rows):
    return (combine_rows(ys, dest, weights=gate),
            (ys, gate, row_pick, n_valid, n_real, dest))


def _moe_combine_bwd(block_rows, res, dout):
    ys, gate, row_pick, n_valid, n_real, dest = res
    t, k = dest.shape
    # a pick's row of the source is its token's cotangent times its gate,
    # so the dispatch needs no gate in buffer order.  The product is made
    # as lane tiles, pick-major, and held at that shape: left to itself XLA
    # merges [k, T] first and then writes the broadcast cotangent out in
    # float32 before it multiplies (1.8 ms a call at 32,768 picks x 2048)
    tiles = _as_tiles(dout)
    scaled = jax.lax.optimization_barrier(
        (gate.T.astype(jnp.float32)[:, :, None, None]
         * tiles.astype(jnp.float32)[None]).astype(dout.dtype))
    dys = dispatch_rows(scaled.reshape(k * t, *tiles.shape[1:]),
                        row_pick % k * t + row_pick // k, n_valid, n_real,
                        block_rows)
    dgate = combine_rows(ys, dest, dot=dout).astype(gate.dtype)
    return dys.astype(ys.dtype), dgate, None, None, None, None


moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)
