"""Paged decode attention (Pallas TPU kernel).

The TPU-native replacement for the reference's ragged decode kernels
(``inference/v2/kernels/ragged_ops``): one query token per sequence
attends over that sequence's KV *pages in place* — the layer and the page
table are scalar-prefetch operands and each grid step's K/V block is
addressed ``k_pool[layer, page_table[b, jp]]`` directly, so the padded
[B, S, KVH, D] gather the XLA fallback materializes per layer per token
never exists, and the serving programs hand the kernel the whole pool they
carry without slicing a layer out of it.

Layout: q [B, KVH, G, D] (GQA groups folded next to their kv head);
pools [L, P, ps, KVH*D] as the engine stores them, read as page blocks
``(1, ps, KVH*D)`` of the ``[L*P, ps, KVH*D]`` view (merging the two MAJOR
dimensions moves nothing under the TPU's tiled layouts; merging the two
minor ones, KVH and D, is a relayout of the pool — which is why the pool is
stored merged); page_table [B, MP] int32 (trash-filled past each
sequence's pages); positions [B] int32 (slot of the CURRENT token —
slots > position are masked, so trash pages beyond the length are
harmless).  Online softmax accumulates across the page grid axis in VMEM
scratch; the output block is written on the last page step.
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


def _decode_kernel(pt_ref, pos_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                   ps, scale, kvh, quant, alibi):
    """One (sequence, page) grid step: every kv head of the page against
    its query group.  The page block is [ps, KVH*D] — head ``h`` is the
    lane slice ``[h*D, (h+1)*D)``, a tile-aligned view (D a multiple of
    128), where a per-head block ``(1, ps, 1, D)`` over the pool would put
    a size-1 block on the second-minor (KVH) dim, which Mosaic refuses."""
    rest = list(rest)
    sl_ref = rest.pop(0) if alibi else None
    ks_ref, vs_ref = (rest.pop(0), rest.pop(0)) if quant else (None, None)
    o_ref, m_scr, l_scr, acc_scr = rest
    b, jp = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]
    g = q_ref.shape[-2]
    pos = pos_ref[b]

    @pl.when(jp == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages wholly past the current token hold nothing visible (trash
    # rows of the table all name one page, so they move no data either)
    @pl.when(jp * ps <= pos)
    def _():
        slots = jp * ps + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        for h in range(kvh):
            q = q_ref[0, h]                           # [G, D]
            k = k_ref[0, :, h * d:(h + 1) * d]        # [ps, D]
            v = v_ref[0, :, h * d:(h + 1) * d]
            if quant:  # int8 codes * per-(slot, head) scale, in VMEM
                k = (k.astype(jnp.float32)
                     * ks_ref[0, :, h:h + 1]).astype(q.dtype)
                v = (v.astype(jnp.float32)
                     * vs_ref[0, :, h:h + 1]).astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [G, ps]
            if alibi:
                # ALiBi distance penalty from page-slot indices (bloom)
                s = s - sl_ref[h] * (pos - slots).astype(jnp.float32)
            s = jnp.where(slots <= pos, s, NEG_INF)
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(jp == pl.num_programs(1) - 1)
    def _():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, page_table, positions,
                           k_scale=None, v_scale=None, alibi_slopes=None,
                           layer=None):
    """q: [B, NH, D]; pools: the engine's ``[L, P, ps, KVH*D]`` read at
    int32 scalar ``layer`` (int8 codes when ``k_scale``/``v_scale``
    ``[L, P, ps, KVH]`` given), or with ``layer=None`` one layer's
    ``[P, ps, KVH, D]`` (scales ``[P, ps, KVH]``); page_table: [B, MP]
    int32; positions: [B] int32; ``alibi_slopes``: optional [NH] per-head
    ALiBi slopes (bias built in-kernel from slot indices).
    Returns [B, NH, D]."""
    B, NH, D = q.shape
    if layer is None:
        # one layer's pool: merging KVH and D relayouts it, which is only
        # acceptable because nothing on the serving path comes this way
        P, ps = k_pool.shape[:2]
        k_pool, v_pool = (x.reshape(1, P, ps, -1) for x in (k_pool, v_pool))
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    L, P, ps, F = k_pool.shape
    MP = page_table.shape[1]
    KVH = F // D
    assert KVH * D == F and NH % KVH == 0
    quant = k_scale is not None
    G = NH // KVH
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, KVH, G, D)

    alibi = alibi_slopes is not None
    q_spec = pl.BlockSpec((1, KVH, G, D),
                          lambda b, jp, pt, pos, lyr: (b, 0, 0, 0))

    # the layer and page-table lookup: this block IS the page (all kv
    # heads of it), row layer * P + page of the [L*P, ps, ...] view
    def page_index(b, jp, pt, pos, lyr):
        return (lyr[0] * P + pt[b, jp], 0, 0)

    page_spec = pl.BlockSpec((1, ps, F), page_index)
    in_specs = [q_spec, page_spec, page_spec]
    args = [qg, k_pool.reshape(L * P, ps, F), v_pool.reshape(L * P, ps, F)]
    if alibi:
        # rides right after k/v so the kernel pops it off *rest first
        in_specs.append(pl.BlockSpec(
            (KVH, G, 1), lambda b, jp, pt, pos, lyr: (0, 0, 0)))
        args.append(jnp.asarray(alibi_slopes, jnp.float32)
                    .reshape(KVH, G, 1))
    if quant:
        scale_spec = pl.BlockSpec((1, ps, KVH), page_index)
        in_specs += [scale_spec, scale_spec]
        args += [k_scale.reshape(L * P, ps, KVH),
                 v_scale.reshape(L * P, ps, KVH)]

    kernel = pl.pallas_call(
        functools.partial(_decode_kernel, ps=ps, scale=scale, kvh=KVH,
                          quant=quant, alibi=alibi),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, MP),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((KVH, G, 1), jnp.float32),
                pltpu.VMEM((KVH, G, 1), jnp.float32),
                pltpu.VMEM((KVH, G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pallas_interpret(),
        name="dstpu_paged_decode",
    )
    out = kernel(page_table, positions,
                 jnp.asarray(layer, jnp.int32).reshape(1), *args)
    return out.reshape(B, NH, D)
