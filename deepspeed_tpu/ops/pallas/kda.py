"""Delta-rule linear attention with a per-channel decay (KDA): the chunkwise
scan for prefill chunks and the one-token step for decode rows (Pallas TPU).

Per head, with a state ``S`` in R^{K x V} kept in float32:

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                     a_t = exp(g_t) in (0, 1], b_t in (0, 2)

The state is stored **value-major**, ``St = S^T`` of shape ``[V, K]``: the
decay then scales lanes (a row broadcast) and ``S^T q`` is a lane
reduction, in both kernels.

``dstpu_kda_chunk`` — one sequence's prefill chunk from a given state.  The
chunk is cut into sub-chunks of ``sub`` tokens.  With ``G`` the inclusive
cumulative log-decay inside a sub-chunk and ``u_t = b_t (v_t - S~_{t-1}^T
k_t)`` the recurrence is ``S_t = diag(a_t) S_{t-1} + k_t u_t^T``, so

    U      = (I + diag(b) tril(A, -1))^{-1} diag(b) (V - (K * e^G) S_0)
    O      = (Q * e^G) S_0 + tril(B, 0) U
    S_end  = diag(e^{G_end}) S_0 + (K * e^{G_end - G})^T U
    A[t,i] = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]},  B likewise with q_t

``A`` and ``B`` are built column by column from the *differences* ``G_t -
G_i <= 0`` — never from ``e^{-G_i}``, which overflows where a channel
forgets fast — and the unit-triangular inverse is the finite product
``(I + M)(I + M^2)(I + M^4)...`` of its nilpotent part.  A token with ``g =
0`` and ``b = 0`` leaves the state as it was: that is how a caller pads.

``dstpu_kda_step`` — one token for the decode rows **that decode**: the state
pool goes in whole and comes back aliased, and the grid walks a compacted
list of the active rows and ends with it — each one's state read from its
slot and written back to it, the layer a scalar-prefetch operand.  An
inactive row's state is neither read nor written and no slot-pool-sized
copy exists (``ops/pallas/ssm.py``'s step kernel takes the same ``active``
mask and has the same contract).

``kda_chunk_xla`` / ``kda_step_xla`` are the same mathematics as XLA
programs (a token-by-token ``lax.scan``): what the CPU test tier runs by
default, as ``model_runner._gather_window_attend`` is for paged attention.
On the chip the serving programs call the kernels, always.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...utils.platform import pallas_interpret

_HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ XLA forms
def kda_step_xla(q, k, v, g, beta, st):
    """One token.  q, k, g: [..., H, K]; v: [..., H, V]; beta: [..., H];
    st: [..., H, V, K] float32.  Returns (o [..., H, V] float32, st)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    st = st * jnp.exp(g)[..., None, :]
    u = beta[..., None] * (v - jnp.sum(st * k[..., None, :], axis=-1))
    st = st + u[..., :, None] * k[..., None, :]
    return jnp.sum(st * q[..., None, :], axis=-1), st


def kda_chunk_xla(q, k, v, g, beta, st):
    """A chunk, token by token.  q, k, g: [C, H, K]; v: [C, H, V]; beta:
    [C, H]; st: [H, V, K] float32.  Returns (o [C, H, V] float32, st)."""
    def body(st, xs):
        o, st = kda_step_xla(*xs, st)
        return st, o

    st, o = jax.lax.scan(body, st.astype(jnp.float32), (q, k, v, g, beta))
    return o, st


# ------------------------------------------------------------ the chunk kernel
def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, o_ref, s1_ref,
                  st_scr, *, sub):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        st_scr[...] = s0_ref[0]

    f32 = jnp.float32
    q = q_ref[0].astype(f32)          # [sub, K]
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)          # [sub, V]
    g = g_ref[0]                      # [sub, K] float32, <= 0
    beta = b_ref[0]                   # [sub, 1] float32
    st = st_scr[...]                  # [V, K]

    row = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (sub, sub), 1)
    # inclusive cumulative log-decay: a lower-triangular sum over rows
    G = _dot((row >= col).astype(f32), g, ((1,), (0,)))      # [sub, K]

    A = jnp.zeros((sub, sub), f32)    # k_t . k_i under the decay between
    B = jnp.zeros((sub, sub), f32)    # q_t . k_i likewise
    for i in range(sub):
        w = k[i:i + 1] * jnp.exp(jnp.minimum(G - G[i:i + 1], 0.0))
        A = jnp.where(col == i, jnp.sum(k * w, axis=-1, keepdims=True), A)
        B = jnp.where(col == i, jnp.sum(q * w, axis=-1, keepdims=True), B)

    eG = jnp.exp(G)
    rhs = beta * (v - _dot(k * eG, st, ((1,), (1,))))        # [sub, V]
    # (I + L)^{-1}, L = diag(beta) tril(A, -1): with M = -L nilpotent,
    # sum_j M^j = (I + M)(I + M^2)(I + M^4)...
    M = -beta * jnp.where(row > col, A, 0.0)
    eye = (row == col).astype(f32)
    inv, n = eye + M, 2
    while n < sub:
        M = _dot(M, M, ((1,), (0,)))
        inv = _dot(inv, eye + M, ((1,), (0,)))
        n *= 2
    U = _dot(inv, rhs, ((1,), (0,)))                         # [sub, V]

    o = (_dot(q * eG, st, ((1,), (1,)))
         + _dot(jnp.where(row >= col, B, 0.0), U, ((1,), (0,))))
    o_ref[0] = o.astype(o_ref.dtype)

    g_end = G[sub - 1:sub]                                   # [1, K]
    st = (st * jnp.exp(g_end)
          + _dot(U, k * jnp.exp(g_end - G), ((0,), (0,))))   # [V, K]
    st_scr[...] = st

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        s1_ref[0] = st


def kda_chunk(q, k, v, g, beta, st, sub: int = 16):
    """The chunkwise scan over one sequence's chunk.  q, k: [C, H, K]; v:
    [C, H, V]; g: [C, H, K] float32 log-decay; beta: [C, H] float32; st:
    [H, V, K] float32, the state before the chunk.  ``C`` is a multiple of
    ``sub``.  Returns (o [C, H, V] float32, state after [H, V, K])."""
    C, H, K = q.shape
    V = v.shape[-1]
    assert C % sub == 0, (C, sub)
    hm = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731  head-major
    tok = lambda d: pl.BlockSpec((1, sub, d), lambda h, j: (h, j, 0))  # noqa: E731
    state = pl.BlockSpec((1, V, K), lambda h, j: (h, 0, 0))
    o, st = pl.pallas_call(
        functools.partial(_chunk_kernel, sub=sub),
        grid=(H, C // sub),
        in_specs=[tok(K), tok(K), tok(V), tok(K), tok(1), state],
        out_specs=[tok(V), state],
        out_shape=[jax.ShapeDtypeStruct((H, C, V), jnp.float32),
                   jax.ShapeDtypeStruct((H, V, K), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_kda_chunk",
    )(hm(q), hm(k), hm(v), hm(g.astype(jnp.float32)),
      hm(beta.astype(jnp.float32))[..., None], st.astype(jnp.float32))
    return hm(o), st


# ------------------------------------------------------------- the step kernel
#: heads of one row a grid step takes: a block of the state is ``_HEADS * V *
#: K * 4`` bytes (2 MiB at 128 x 128), in VMEM four times over — fetched and
#: written back, both double-buffered; 64 heads would need the scoped VMEM
#: limit raised for 2 % of a call's time (PERF.md §6, PR 39)
_HEADS = 32


def _step_kernel(layer_ref, rows_ref, q_ref, k_ref, v_ref, g_ref, b_ref,
                 s_ref, o_ref, so_ref, *, heads):
    del layer_ref, rows_ref  # consumed by the index maps
    f32 = jnp.float32
    V, K = s_ref.shape[-2:]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (V, V), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (V, V), 1)).astype(f32)
    for h in range(heads):
        q = q_ref[0, h:h + 1].astype(f32)       # [1, K]
        k = k_ref[0, h:h + 1].astype(f32)
        v = v_ref[0, h:h + 1].astype(f32)       # [1, V]
        beta = b_ref[0, h:h + 1]                # [1, 1]
        st = s_ref[0, h] * jnp.exp(g_ref[0, h:h + 1])            # [V, K]
        # a row to a column: the diagonal of its broadcast
        v_col = jnp.sum(eye * v, axis=-1, keepdims=True)         # [V, 1]
        u = beta * (v_col - jnp.sum(st * k, axis=-1, keepdims=True))
        st = st + u * k
        so_ref[0, h] = st
        o_col = jnp.sum(st * q, axis=-1, keepdims=True)          # [V, 1]
        o_ref[0, h:h + 1] = jnp.sum(eye * o_col, axis=0,
                                    keepdims=True).astype(o_ref.dtype)


@jax.jit
def kda_step(q, k, v, g, beta, pool, layer, active):
    """One token for every decode row that decodes, the state pool updated
    in place.

    q, k: [B, H, K]; v: [B, H, V]; g: [B, H, K] float32; beta: [B, H]
    float32; pool: ``[L, N + 1, H, V, K]`` float32, row ``b``'s state in
    slot ``b`` (``B <= N``); layer: int32 scalar; active: [B] bool (the
    calling convention of ``ssm.ssm_step``).  Returns (o [B, H, V] float32,
    zero for a row that is not active; pool): nothing of a row that is not
    active is read, and its slot is not written.

    The grid is ``(n, H // _HEADS)`` over the active rows sorted to the
    front, ``n`` their number — a dynamic bound: one compiled program
    whatever ``n`` is, and no grid step for any other row (with no row
    active the call does nothing).  ``ssm_step``'s fixed grid, whose steps
    past ``n`` all name one block of the trash slot, would here have to pin
    the head block past ``n`` too, or those steps alternate between the trash
    slot's blocks and move 2 MiB each; and a skipped step still costs
    ≈ 0.37 µs, 0.1 ms a call at 40 of 128 rows (PERF.md §6, PR 39).  Under
    its own ``jax.jit``: a program that calls it for several layers traces
    and lowers it once."""
    f32 = jnp.float32
    B, H, K = q.shape
    V = v.shape[-1]
    L, N1 = pool.shape[:2]
    hb = min(_HEADS, H)
    assert H % hb == 0 and pool.shape[2:] == (H, V, K) and B <= N1, (
        pool.shape, q.shape)
    # the active rows first, in order; the grid ends with them
    rows = jnp.sort(jnp.where(active, jnp.arange(B), B)).astype(jnp.int32)
    n = jnp.sum(active).astype(jnp.int32)

    def row(i, j, lyr, rows):
        return (rows[i], j, 0)

    def slot(i, j, lyr, rows):
        return (lyr[0] * N1 + rows[i], j, 0, 0)

    per_row = lambda d: pl.BlockSpec((1, hb, d), row)  # noqa: E731
    state = pl.BlockSpec((1, hb, V, K), slot)
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n, H // hb),
            in_specs=[per_row(K), per_row(K), per_row(V), per_row(K),
                      per_row(1), state],
            out_specs=[per_row(V), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, V), f32),
                   jax.ShapeDtypeStruct((L * N1, H, V, K), f32)],
        # the pool (operand 7, after the two scalar operands) IS output 1
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows,
      q, k, v, g.astype(f32), beta.astype(f32)[..., None],
      pool.reshape(L * N1, H, V, K))
    # the rows of o that no grid step wrote hold whatever the buffer held
    return (jnp.where(active[:, None, None], o, 0.0),
            pool.reshape(L, N1, H, V, K))
