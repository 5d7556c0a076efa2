"""Selective state-space scan (Mamba-1): the scan over a prefill chunk and the
one-token step for the decode rows (Pallas TPU).

Per channel ``d`` and state index ``n``, with a state ``s`` kept in float32:

    s_t[n, d] = exp(dt_t[d] * A[n, d]) * s_{t-1}[n, d] + dt_t[d] u_t[d] B_t[n]
    y_t[d]    = sum_n s_t[n, d] C_t[n] + D[d] u_t[d]

``A`` has a value per channel and state index (Mamba-1, not the scalar decay
of Mamba-2), so the recurrence is elementwise over ``[state, channel]`` and
has no matmul form: both kernels run it on the VPU, token by token.  The state
is stored **state-major**, ``[state, channel]``: channels lie on the lanes
(``[channel, 16]`` would fill 16 of 128), ``B_t`` and ``C_t`` broadcast along
them as columns and ``y`` is a sublane reduction.

``dstpu_ssm_chunk`` — one sequence's prefill chunk from a given state: a grid
of (channel blocks, token blocks), the block's state carried in VMEM scratch
across the token axis, ``_TOKENS`` tokens unrolled a grid step.  A token with
``dt = 0`` leaves the state as it was: that is how the padding past the
chunk's real tokens is given (the wrapper zeroes it, whatever it held).

``dstpu_ssm_step`` — one token for the decode rows **that decode**: the state
pool goes in whole and comes back aliased, and the grid walks a compacted
list of the active rows — each one's state read from its slot and written
back to it, the layer a scalar-prefetch operand.  The grid steps past the
last active row all point at the trash slot and compute nothing, so an
inactive row's state is neither read nor written and no slot-pool-sized copy
exists.

``ssm_chunk_xla`` / ``ssm_step_xla`` are the same mathematics as XLA programs
(a token-by-token ``lax.scan``): what the CPU test tier runs by default.  On
the chip the serving programs call the kernels, always.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret

#: tokens a grid step of the chunk kernel unrolls, channels a block holds
_TOKENS = 16
_CHANNELS = 1024


# ------------------------------------------------------------------ XLA forms
def ssm_step_xla(dt, u, b, c, a, d, s):
    """One token.  dt, u: [..., DI]; b, c: [..., N]; a: [N, DI]; d: [DI]; s:
    [..., N, DI] float32.  Returns (y [..., DI] float32, s)."""
    f32 = jnp.float32
    dt, u, b, c = (x.astype(f32) for x in (dt, u, b, c))
    s = (jnp.exp(dt[..., None, :] * a.astype(f32)) * s
         + (dt * u)[..., None, :] * b[..., :, None])
    return jnp.sum(s * c[..., :, None], axis=-2) + d.astype(f32) * u, s


def ssm_chunk_xla(dt, u, b, c, a, d, s):
    """A chunk, token by token.  dt, u: [C, DI]; b, c: [C, N]; s: [N, DI]
    float32.  Returns (y [C, DI] float32, s)."""
    def body(s, xs):
        y, s = ssm_step_xla(*xs, a, d, s)
        return s, y

    s, y = jax.lax.scan(body, s.astype(jnp.float32), (dt, u, b, c))
    return y, s


# ------------------------------------------------------------ the chunk kernel
def _column(row):
    """A ``[1, N]`` row as an ``[N, 1]`` column: the diagonal of its
    broadcast."""
    n = row.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def _chunk_kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                  y_ref, s1_ref, st_scr, *, tokens):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        st_scr[...] = s0_ref[...]

    a, d, s = a_ref[...], d_ref[...], st_scr[...]       # [N, DB] [1, DB]
    for t in range(tokens):
        dt, u = dt_ref[t:t + 1, :], u_ref[t:t + 1, :]   # [1, DB]
        s = jnp.exp(dt * a) * s + (dt * u) * _column(b_ref[t:t + 1, :])
        y_ref[t:t + 1, :] = (jnp.sum(s * _column(c_ref[t:t + 1, :]), axis=0,
                                     keepdims=True) + d * u)
    st_scr[...] = s

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        s1_ref[...] = s


@jax.jit
def _ssm_chunk(dt, u, b, c, a, d, s):
    C, DI = dt.shape
    N = a.shape[0]
    T = _TOKENS if C % _TOKENS == 0 else 8
    DB = _CHANNELS if DI % _CHANNELS == 0 else DI
    assert C % T == 0, (C, T)
    tok = pl.BlockSpec((T, DB), lambda ch, j: (j, ch))
    col = pl.BlockSpec((T, N), lambda ch, j: (j, 0))
    state = pl.BlockSpec((N, DB), lambda ch, j: (0, ch))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, tokens=T),
        grid=(DI // DB, C // T),
        in_specs=[tok, tok, col, col, state,
                  pl.BlockSpec((1, DB), lambda ch, j: (0, ch)), state],
        out_specs=[tok, state],
        out_shape=[jax.ShapeDtypeStruct((C, DI), jnp.float32),
                   jax.ShapeDtypeStruct((N, DI), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, DB), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_ssm_chunk",
    )(dt, u, b, c, a, d, s)


def ssm_chunk(dt, u, b, c, a, d, s, n, kernel: bool = True):
    """The scan over one sequence's chunk.  dt, u: [C, DI]; b, c: [C, N]; a:
    [N, DI]; d: [DI]; s: [N, DI] float32, the state before the chunk; n:
    int32 scalar, the chunk's real tokens — whatever the rows past ``n``
    hold is not read.  ``C`` is a multiple of 8.  ``kernel=False``: the XLA
    form.  Returns (y [C, DI] float32, ``D u = 0`` past ``n``; the state after
    token ``n - 1``)."""
    f32 = jnp.float32
    real = (jnp.arange(dt.shape[0]) < n)[:, None]
    dt, u, b, c = (jnp.where(real, x.astype(f32), 0.0)
                   for x in (dt, u, b, c))
    if not kernel:
        return ssm_chunk_xla(dt, u, b, c, a, d, s)
    return _ssm_chunk(dt, u, b, c, a.astype(f32), d.astype(f32)[None],
                      s.astype(f32))


# ------------------------------------------------------------- the step kernel
def _step_kernel(layer_ref, rows_ref, n_ref, dt_ref, u_ref, b_ref, c_ref,
                 a_ref, d_ref, s_ref, y_ref, so_ref):
    del layer_ref, rows_ref  # consumed by the index maps

    @pl.when(pl.program_id(0) < n_ref[0])
    def _():
        dt, u = dt_ref[0], u_ref[0]                     # [1, DI]
        s = jnp.exp(dt * a_ref[...]) * s_ref[0] + (dt * u) * b_ref[0]
        so_ref[0] = s
        y_ref[0] = (jnp.sum(s * c_ref[0], axis=0, keepdims=True)
                    + d_ref[...] * u)


def ssm_step(dt, u, b, c, a, d, pool, layer, active, kernel: bool = True):
    """One token for every decode row that decodes, the state pool updated
    in place.

    dt, u: [B, DI]; b, c: [B, N]; a: [N, DI]; d: [DI]; pool: ``[L, S + 1, N,
    DI]`` float32, row ``r``'s state in slot ``r`` (``B <= S``; the last slot
    is the trash slot); layer: int32 scalar; active: [B] bool.  Returns (y
    [B, DI] float32, zero for a row that is not active; pool): the state of
    a row that is not active is neither read nor written (``kernel=False``,
    the XLA form, reads every row and writes an inactive one's to the trash
    slot)."""
    f32 = jnp.float32
    B, DI = dt.shape
    N = a.shape[0]
    L, S1 = pool.shape[:2]
    assert pool.shape[2:] == (N, DI) and B < S1, (pool.shape, dt.shape)
    if not kernel:
        y, st = ssm_step_xla(dt, u, b, c, a, d, pool[layer, :B])
        dst = jnp.where(active, jnp.arange(B), S1 - 1)
        return (jnp.where(active[:, None], y, 0.0),
                pool.at[layer, dst].set(st))
    # the active rows first, in order; past them B, which the index maps
    # send to the trash slot and to the spare row of y
    rows = jnp.sort(jnp.where(active, jnp.arange(B), B)).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32).reshape(1)

    def src(i, lyr, rows, n):
        return (jnp.minimum(rows[i], B - 1), 0, 0)

    def slot(i, lyr, rows, n):
        return (lyr[0] * S1 + jnp.where(rows[i] < B, rows[i], S1 - 1), 0, 0)

    row = lambda w: pl.BlockSpec((1, 1, w), src)  # noqa: E731
    col = pl.BlockSpec((1, N, 1), src)
    whole = lambda h: pl.BlockSpec((h, DI), lambda i, *_: (0, 0))  # noqa: E731
    y, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row(DI), row(DI), col, col, whole(N), whole(1),
                      pl.BlockSpec((1, N, DI), slot)],
            out_specs=[pl.BlockSpec((1, 1, DI),
                                    lambda i, lyr, rows, n: (rows[i], 0, 0)),
                       pl.BlockSpec((1, N, DI), slot)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B + 1, 1, DI), f32),
                   jax.ShapeDtypeStruct((L * S1, N, DI), f32)],
        # the pool (operand 9, after the three scalar operands) IS output 1
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pallas_interpret(),
        name="dstpu_ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows, n,
      dt.astype(f32)[:, None], u.astype(f32)[:, None],
      b.astype(f32)[..., None], c.astype(f32)[..., None],
      a.astype(f32), d.astype(f32)[None], pool.reshape(L * S1, N, DI))
    return (jnp.where(active[:, None], y[:B, 0], 0.0),
            pool.reshape(L, S1, N, DI))
