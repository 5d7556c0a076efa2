"""Jitted programs over the paged KV cache.

Reference parity: the ragged kernel set — blocked rotary + KV copy
(inference/v2/kernels/ragged_ops/blocked_kv_rotary), ragged attention via
blocked KV, logits gather (ragged_ops/logits_gather).  On TPU these are
two XLA programs:

* ``paged_prefill`` — one (bucket-padded) prompt: dense causal attention,
  K/V scattered into the sequence's pages.
* ``paged_decode`` — one token for *all* decode slots at once, regardless
  of per-sequence lengths: gather pages by table, mask by length.  This is
  the continuous-batching hot loop; lengths/page tables are data, not
  shapes, so one compiled program serves every batch composition.

Scatters are unconditional: inactive slots and pad chunks write to the
trash page (ragged.py) instead of branching.

The pools are ``[L, P+1, ps, KVH*D]`` (scales ``[L, P+1, ps, KVH]``): the
page block the decode kernel reads, so nothing relayouts them.  Every
program runs its layers through ``_scan_layers``, which carries the whole
pools through the loop; a layer writes ``pool.at[l, ...]`` and reads
``pool[l, table]`` on the carry, and with the pools donated at the jit
boundary XLA updates the one buffer in place.  No operation of a serving
program is pool-sized; the only reshapes between ``KVH*D`` and
``[KVH, D]`` are on the fresh K/V of a call and on a gathered window.

A stack that is a repeated period of layer types (``models/layer_types``)
is scanned period by period, a period's layers unrolled in the body.  Each
program hands ``_scan_layers`` one ``layer_fn`` per mixer; ``l`` counts the
layers *of that mixer*, which is the layer index of the pool leaves that
mixer keeps: ``L`` of the K/V pools is the number of attention layers, and
the state slots of the linear-attention layers (``kda_s`` ``[L_kda, slots+1,
heads, V, K]`` float32, ``kda_conv`` ``[L_kda, slots+1, conv-1, 3*heads*D]``;
slot = decode row, the last slot is the trash slot) ride in the same dict,
donated and updated in place like the pages.

A stack of several runs of periods (``cfg.layer_runs``: Phi-4-mini-flash) is
scanned run by run.  Its state-space layers keep ``ssm_s`` ``[L_mamba,
slots+1, state, inner]`` float32 and a convolution tail ``ssm_conv``; its
window layers keep a ring of the last ``sliding_window`` positions' keys and
values in the slots (``win_k`` / ``win_v`` ``[L_swa, slots+1, window,
KVH*D]``: no pages), read by the paged decode kernel as ``window / page_size``
pages a slot; one full-attention layer writes the only pages, and the
cross-attention layers read them.  Two values cross layers in the carry
beside the pools: the last state-space layer's scan output (the gated memory
units' memory) and, in the chunk program, the cut to the prompt's last token,
after which the cross-decoder runs for one row.

A stack of latent-attention layers (``mla``: Mistral-Small-4) keeps ONE page
leaf, ``latent`` ``[L, P+1, ps, W]``: a token's normalised latent and, beside
it, the rotary key all heads share, already rotated (``W``: whole lane tiles,
``layer_types.latent_width``) — no K pool and no V pool.  The chunk program
writes the chunk's rows, gathers the window's and *expands* them (``[k_nope |
v] = c W_ukv`` per head) into keys and values for the flash kernel; the decode
program *absorbs* the up-projections into the query and the output and attends
the latent rows themselves (``ops/pallas/mla_attention``), each visible page
fetched once.  Nothing expanded outlives a call.

A stack of grouped-query layers whose cache follows the layer's type
(``gqa_full`` / ``gqa_window``: MiMo-V2-Flash) keeps pages for its full layers
alone — ``k`` ``[L_full, P+1, ps, KVH * 192]``, rotated, each row ``[the heads'
plain lanes | the heads' rotary lanes]`` (``paged_attention.split_keys``: 192
is one and a half lane tiles, and laid out so no head starts inside one),
beside ``v`` ``[L_full, P+1, ps, KVH * 128]``, scaled — and for its window
layers rings in the slots, ``win_k`` / ``win_v`` ``[L_win, slots+1, window,
KVH_win * 192 | 128]`` of another head count, position ``t`` at row ``t %
window``.  Keys are stored rotated with the type's base, so a ring's order is
not needed, only which rows are live.  Its first layer is a run of its own
with a dense feed-forward part: a served layer's feed-forward part is what
its parameters are (``_ffn``).

A residual of several streams (``cfg.hc_mult`` > 1: Xing4.0's
manifold-constrained hyper-connections) rides in the same carry, the streams
side by side: ``x [B, T, n * H]``, stream ``i`` the lanes ``i * H .. (i + 1) *
H`` (a dimension of ``n`` = 4 before ``H`` would be a second-minor one, which
the device pads to a tile of 8 or 16 rows).  The embedding is copied to the
``n`` streams (``_streams_in``) and they are summed before the final norm
(``_streams_out``); every sublayer reads ``H_pre X`` (``_stream_read``) and
``H_res X + H_post^T y`` goes back (``_stream_write``) — the one pair through
which every program adds a sublayer's output to the residual, so that the
formulations cannot diverge.  With one stream the pair is ``x`` and ``x + y``
and traces no operation of its own: a one-stream model's programs are what
they were.

A stack of EVA-attention layers (``eva``: EvaByte) keeps both of its caches in
the K and V page leaves: the open window's keys and values, a row a position,
and one pooled summary row a whole chunk of ``cfg.eva_chunk`` positions
(``ragged.EvaRows``: a page is one chunk of exact rows or a page of summaries;
the host's table row is ``[summary pages | open-window pages]``).  The decode
program writes a row, pools the page it completed at a chunk's last position
(``layer_types.eva_pool``) and attends ``[visible summary pages | open pages]``
— a table composed on the device from the row's position, so a row whose
window closes goes on in the same program with ``window / chunk`` more
summaries visible and an empty window — through the paged decode kernel under
the name ``dstpu_eva_decode``; the chunk program attends ``[visible summaries
| the open window's rows | the chunk, causal]``, the first two gathered by a
table the host right-aligns, through the flash kernel with the unused front
masked (``k_first``).  The head is ``cfg.pred_heads`` heads wide, in float32.

A model that generates by diffusion over blocks (``cfg.block_length``:
SDAR-MoE) has ``paged_block_pass`` in ``paged_decode``'s place — a block of
``B`` positions a row, its K/V written in place to its slots of the row's page,
every one of its queries attending all ``start + B`` positions through the
paged decode kernel with the block folded into the head axis, and the reveal
rule on the device (``reveal_tokens``) — and the chunk program under the block
mask (causal between blocks, bidirectional inside one), which for such a model
ends at the last layer's K/V write.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from ...models.layer_types import (GqaShape, eva_pool, gqa_shape,
                                   latent_width, layers_of, page_layers,
                                   run_config, served_runs)
from ...models import transformer
from ...models.transformer import (MODEL_AXIS, TransformerConfig, _mm,
                                   _norm, _repeat_kv, alibi_slopes,
                                   logits_fn, mlp_block, mlp_delta,
                                   rope_interleaved, yarn_inv_freq)
from ...telemetry.regions import region
from ...ops.pallas.paged_attention import (merged_keys, split_keys,
                                           split_queries)


def _by_head(cfg: TransformerConfig, h, leaf, heads: int, dim: int,
             bias=None):
    """A projection whose result is used by head, as every paged program
    makes it: the plain product, pinned, and only then the view by head
    (``transformer.head_projection``) — no copy of the weight a call."""
    return transformer.head_projection(cfg, h, leaf, bias, heads, dim,
                                       pinned=True)


def attn_qkv(cfg: TransformerConfig, layer, x, positions):
    """``transformer.attn_qkv`` over pinned products."""
    return transformer.attn_qkv(cfg, layer, x, positions, pinned=True)


def _use_paged_kernel() -> bool:
    """Pallas kernels, always, on TPU.  On the CPU test tier the XLA
    gather path is the default and DSTPU_PAGED_KERNEL=1 forces the
    kernels in interpret mode (read at trace time)."""
    import os

    from ...utils.platform import on_tpu

    forced = os.environ.get("DSTPU_PAGED_KERNEL")
    if on_tpu():
        if forced == "0":
            raise RuntimeError(
                "DSTPU_PAGED_KERNEL=0 asks for the XLA gather path on TPU; "
                "the chip serves through the paged kernel only")
        return True
    return forced == "1"


#: the pool leaves laid out in pages ``[L, P+1, ps, ...]`` (beside them ride
#: state slots and counters, which no page gather or copy touches)
PAGE_LEAVES = ("k", "v", "k_scale", "v_scale", "latent")


def _page_geometry(pools) -> Tuple[int, int]:
    """(page_size, the trash page's index) of the carried pools, from
    whichever page leaf the model's layer types declared."""
    leaf = pools["k"] if "k" in pools else pools["latent"]
    return leaf.shape[2], leaf.shape[1] - 1


def _kv_quantize(x):
    """[..., KVH, D] -> (int8 codes, fp32 scale [..., KVH]) per head."""
    s = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0,
                    1e-8)
    q = jnp.round(x.astype(jnp.float32) / s[..., None]).astype(jnp.int8)
    return q, s.astype(jnp.float32)


def _period_body(types, per, before, first, layer_fns):
    """The scan body of one run of ``_scan_layers``: a period's layers,
    unrolled.  ``per``: layers of each mixer in a period; ``before``: layers
    of each mixer in the runs before this one; ``first``: the stack index of
    the run's first layer."""
    def period_body(carry, inputs):
        layers, p = inputs
        x, pools, cross = carry
        seen = dict.fromkeys(per, 0)
        for j, (layer, t) in enumerate(zip(layers, types)):
            m = t.mixer
            # (a mixer that comes once a period counts periods: no index
            # arithmetic, so a homogeneous stack lowers as it always has)
            l = p if per[m] == 1 else p * per[m] + seen[m]
            if before.get(m):
                l = l + before[m]
            seen[m] += 1
            with region(_MIXER_GLUE.get(m, "attn_glue")):
                if t.crosses:
                    x, pools, aux, cross = layer_fns[m](
                        layer, l, x, pools, cross,
                        first + p * len(types) + j)
                else:
                    x, pools, aux = layer_fns[m](layer, l, x, pools)
            # (a prologue's dense layer beside an expert share has none)
            if "moe_stats" in pools and "router" in layer["mlp"]:
                pools = dict(pools, moe_stats=pools["moe_stats"] + aux)
        return (x, pools, cross), None

    return period_body


_EXPERT_MATRICES = ("w_gate", "w_up", "w_down")

#: the region of what a mixer's layer function writes itself, outside the
#: shared pieces that name their own (``attn_qkv``, ``_attn_out``, ``_ffn``):
#: cache writes, page gathers, masks and the XLA forms of attention, or what
#: surrounds a recurrent-state kernel
_MIXER_GLUE = {"kda": "state_glue", "mamba": "state_glue",
               "gmu": "state_glue", "eva": "eva_glue"}


def _experts_left_stacked(period_body, trees):
    """An expert share scanned over several periods: take the expert matrices
    ``[n, E, ...]`` out of what the scan slices a layer at a time and leave
    them to the body whole, as ``[n * E, ...]`` beside ``expert_first = p *
    E`` (``moe.sharded_moe._expert_ffn_blocks``).  A slice of the stack as
    the operand of a Mosaic call is a copy XLA makes first: 3 x 268 MB a layer
    call at Mistral-Small-4's share, 19.6 ms of a 52.9 ms decode program
    (PERF.md, PR 40).  -> (the body, the trees the scan slices)."""
    held = tuple({k: t["mlp"][k] for k in _EXPERT_MATRICES
                  if getattr(t.get("mlp", {}).get(k), "ndim", 0) == 4}
                 for t in trees)  # plain arrays only: not quantized leaves
    sliced = tuple(dict(t, mlp={k: v for k, v in t["mlp"].items()
                                if k not in h}) if h else t
                   for t, h in zip(trees, held))

    def body(carry, inputs):
        layers, p = inputs
        layers = tuple(
            dict(layer, mlp=dict(
                layer["mlp"], expert_first=p * h["w_up"].shape[1],
                **{k: w.reshape(-1, *w.shape[2:]) for k, w in h.items()}))
            if h else layer for layer, h in zip(layers, held))
        return period_body(carry, (layers, p))

    return body, sliced


def _scan_layers(cfg: TransformerConfig, params, pools, x, layer_fns,
                 cross=None, until=None):
    """The layer loop of every paged program: ``layer_fns[mixer](layer, l,
    x, pools) -> (x, pools, aux)`` over ``params["layers"]`` with the WHOLE
    pools in the carry.  The pools are never a per-layer operand or a
    stacked output of the scan — that form makes XLA slice a layer out,
    update the slice and write it into a second pool-sized buffer — so with
    the pools donated the writes land in the caller's buffer.

    The stack is a sequence of runs (``layer_types.served_runs``), each
    scanned over its periods of layer types, a period's layers unrolled in
    the body (a homogeneous stack is one run of a period of one); ``l`` is
    the index among the model's layers of that mixer.  A run of one period
    among several is not scanned, so its layers may change the shape of
    ``x``.  ``aux`` is what the layer's feed-forward part returned beside its
    output (``mlp_block``): for an expert share its int32 counters
    (``moe.sharded_moe.MOE_COUNTERS``), added to the pools' ``moe_stats``
    leaf where the cache manager made one.

    A type that ``crosses`` has ``layer_fns[mixer](layer, l, x, pools, cross,
    i) -> (x, pools, aux, cross)``: ``cross`` is the dict of values that
    cross layers, carried beside the pools, ``i`` the layer's index in the
    stack.  ``until``: stop after the run that holds this mixer."""
    runs = served_runs(cfg)
    if cfg.hc_mult > 1:
        unread = sorted({t.mixer for types, _ in runs for t in types}
                        - set(_STREAM_MIXERS))
        if unread or cfg.parallel_block:
            raise NotImplementedError(
                f"a residual of hc_mult={cfg.hc_mult} streams is served for "
                f"sequential blocks of the mixers {_STREAM_MIXERS}: "
                + (f"the {unread} layer functions read the residual as it is"
                   if unread else "a parallel block has one read for two "
                   "sublayers"))
    stack = params["layers"]
    if not cfg.layer_runs:  # one run: the stack is its period's trees
        stack = (stack if isinstance(stack, tuple) else (stack,),)
    cross = dict(cross or {})
    before = {}   # layers of each mixer in the runs before this one
    first = 0     # the stack index of this run's first layer
    for (types, n), trees in zip(runs, stack):
        per = {t.mixer: sum(u.mixer == t.mixer for u in types) for t in types}
        period_body = _period_body(types, per, dict(before), first, layer_fns)
        # (the loop under ``stack``: its own slices of the stacked weights)
        with region("stack"):
            if n == 1 and len(runs) > 1:
                (x, pools, cross), _ = period_body(
                    (x, pools, cross),
                    (jax.tree_util.tree_map(lambda a: a[0], trees), 0))
            else:
                if cfg.moe_held_count and n > 1:
                    period_body, trees = _experts_left_stacked(period_body,
                                                               trees)
                (x, pools, cross), _ = jax.lax.scan(
                    period_body, (x, pools, cross), (trees, jnp.arange(n)))
        for m, k in per.items():
            before[m] = before.get(m, 0) + k * n
        first += n * len(types)
        if until is not None and until in per:
            break
    return x, pools


def _pool_write(pools, l, idx, k, v):
    """Write fresh K/V ``[..., KVH, D]`` into layer ``l`` of the carried
    pools at ``idx`` — ``(rows,)`` for whole pages ``[n, ps, KVH, D]``,
    ``(page_idx, off)`` for single tokens — quantizing when the pool is
    int8.  The ``KVH*D`` merge is on the call's own K/V, never the pool."""
    out = dict(pools)
    for name, x in (("k", k), ("v", v)):
        if name + "_scale" in pools:
            x, scale = _kv_quantize(x)
            out[name + "_scale"] = (
                pools[name + "_scale"].at[(l, *idx)].set(scale))
        out[name] = pools[name].at[(l, *idx)].set(
            x.reshape(*x.shape[:-2], -1).astype(pools[name].dtype))
    return out


def _pool_window(pools, l, table, kv_heads):
    """Layer ``l``'s pages ``table [..., MP]`` as K, V ``[..., MP*ps, KVH,
    D]`` — a window-sized gather, dequantized to fp32 when the pool is
    int8."""
    out = []
    for name in ("k", "v"):
        w = pools[name][l, table]  # [..., MP, ps, KVH*D]
        w = w.reshape(*table.shape[:-1], -1, kv_heads,
                      w.shape[-1] // kv_heads)
        if name + "_scale" in pools:
            sc = pools[name + "_scale"][l, table]
            w = (w.astype(jnp.float32)
                 * sc.reshape(*w.shape[:-1])[..., None])
        out.append(w)
    return out


# --------------------------- a residual of several streams (Xing4.0: mHC)
#: the mixers whose layer functions read the residual through
#: ``_stream_read``; the others' read ``x`` as it is and are refused by name
#: for a residual of several streams (``_scan_layers``)
_STREAM_MIXERS = ("attn", "mla", "gqa_full", "gqa_window")


def _streams_in(cfg: TransformerConfig, x):
    """The embedding ``[..., H]`` as the residual the layers carry: copied to
    each of the ``hc_mult`` streams ``[..., n * H]`` (one stream: ``x``)."""
    if cfg.hc_mult <= 1:
        return x
    with region("mhc"):
        return jnp.tile(x, cfg.hc_mult)


def _streams_out(cfg: TransformerConfig, x):
    """The residual ``[..., n * H]`` as the final norm reads it: the streams
    summed, in float32 (one stream: ``x``)."""
    if cfg.hc_mult <= 1:
        return x
    with region("mhc"):
        return sum(s.astype(jnp.float32)
                   for s in jnp.split(x, cfg.hc_mult, axis=-1)).astype(x.dtype)


def _sinkhorn(rows, rounds: int, eps: float):
    """``rows[i][j]``: the positive entries of an ``n x n`` matrix a token,
    each an array over the tokens.  ``rounds`` times: every row divided by
    its sum + ``eps``, then every column by its sum + ``eps``.  Entry by
    entry: a round is some sixty elementwise operations over arrays with the
    tokens on the lanes, which XLA fuses into one kernel — as ``[tokens, n,
    n]`` with reductions over the minor dimensions a round is several kernels
    over tiles that are 1/64 full.  A loop of four rounds a trip: unrolled
    whole, the 1,300 operations of 20 rounds compile for 20 s a sublayer."""
    n = len(rows)

    def one_round(_, rows):
        rows = [[e / (sum(r) + eps) for e in r] for r in rows]
        cols = [sum(rows[i][j] for i in range(n)) + eps for j in range(n)]
        return tuple(tuple(rows[i][j] / cols[j] for j in range(n))
                     for i in range(n))

    return jax.lax.fori_loop(0, rounds, one_round,
                             tuple(tuple(r) for r in rows), unroll=4)


def _stream_read(cfg: TransformerConfig, layer, x, part: str):
    """What a sublayer (``part``: "mixer" | "ffn") reads of the residual ``x
    [B, T, n * H]`` -> (``h [B, T, H]``, the coefficients ``_stream_write``
    takes); with one stream ``(x, None)`` and nothing traced.

    ``m = RMSNorm(vec X) phi`` (no learned scale), ``H_pre = sigmoid(a_pre
    m[:n] + b_pre)``, ``H_post = 2 sigmoid(a_post m[n:2n] + b_post)``, ``H_res
    = Sinkhorn(exp(clip(a_res mat(m[2n:]) + B_res, -+hc_clamp)))``; ``h =
    H_pre X``.  All in float32 from the stored stream.  The norm's factor is
    applied AFTER the projection (``(x phi) r`` for ``(x r) phi``): the stored
    stream and ``phi`` then enter the product as they are stored, exact in a
    float32 accumulator, where a normed float32 operand would be rounded to
    the matrix unit's input type first."""
    if cfg.hc_mult <= 1:
        return x, None
    f32 = jnp.float32
    n, p = cfg.hc_mult, layer["hc"][part]
    with region("mhc"):
        r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(f32)), -1)
                          + cfg.norm_eps)
        # [2n + n^2, B, T]: the tokens on the lanes
        m = jnp.einsum("btk,kw->wbt", x, p["phi"].astype(x.dtype),
                       preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST) * r
        alpha = p["alpha"].astype(f32)
        b = p["b"].astype(f32)[:, None, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + b[n:2 * n])
        e = jnp.exp(jnp.clip(alpha[2] * m[2 * n:] + b[2 * n:],
                             -cfg.hc_clamp, cfg.hc_clamp))
        res = _sinkhorn([[e[i * n + j] for j in range(n)] for i in range(n)],
                        cfg.hc_sinkhorn_iters, cfg.hc_eps)
        xs = jnp.split(x, n, axis=-1)
        h = sum(pre[i][..., None] * xs[i].astype(f32) for i in range(n))
        return h.astype(x.dtype), (post, res)


def _stream_write(x, y, mix):
    """The residual after a sublayer whose output is ``y [B, T, H]``: ``H_res
    X + H_post^T y`` (stream ``i`` gets ``sum_j H_res[i, j] X_j + H_post[i]
    y``), ``mix`` what ``_stream_read`` returned beside ``h``; with one stream
    (``mix`` None) ``x + y``."""
    if mix is None:
        return x + y
    post, res = mix
    n = len(res)
    with region("mhc"):
        f32 = jnp.float32
        xs = [a.astype(f32) for a in jnp.split(x, n, axis=-1)]
        yf = y.astype(f32)
        return jnp.concatenate(
            [sum(res[i][j][..., None] * xs[j] for j in range(n))
             + post[i][..., None] * yf for i in range(n)],
            axis=-1).astype(x.dtype)


def _ffn(cfg: TransformerConfig, layer, x):
    """mlp_block shared with the training forward -> (x, aux): inference has
    no use for an auxiliary loss; an expert share's counters come in its
    place (``_scan_layers``).  A layer of a model with experts that holds no
    router is a prologue's (``layer_types.served_run_configs``): dense, at
    the prologue's width."""
    if cfg.moe_experts and "router" not in layer["mlp"]:
        cfg = run_config(cfg, "dense")
    if cfg.hc_mult > 1:
        h, mix = _stream_read(cfg, layer, x, "ffn")
        y, aux = mlp_delta(cfg, layer, h, training=False)
        return _stream_write(x, y, mix), aux
    return mlp_block(cfg, layer, x, training=False)


def _alibi_bias(cfg: TransformerConfig, qpos, kpos):
    """ALiBi score bias: qpos [..., Q], kpos [..., K] (leading dims
    broadcastable against batch) -> [..., NH, Q, K].  One definition for
    all three paged programs so the formulations cannot diverge."""
    rel = (qpos[..., :, None] - kpos[..., None, :]).astype(jnp.float32)
    return -alibi_slopes(cfg.n_heads)[:, None, None] * rel[..., None, :, :]


def _attn_out(cfg: TransformerConfig, layer, x, attn, pools, read=None):
    """Output projection + residual/parallel-block epilogue shared by the
    prefill/chunk/decode scan bodies; returns what a layer_fn returns
    (``_scan_layers``).  ``read``: what ``_stream_read`` gave the mixer of
    the residual ``x`` (None: ``x`` itself, one stream)."""
    seen, mix = read or (x, None)
    if "wg" in layer["attn"]:  # gated output: wo (attn * sigmoid(wg h))
        h = _norm(seen, layer["norm1"]["scale"], layer["norm1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        gate = jax.nn.sigmoid(_mm(cfg, h, layer["attn"]["wg"], None,
                                  MODEL_AXIS).astype(jnp.float32))
        if gate.shape[-1] != attn.shape[-1]:  # one scalar a head
            gate = jnp.repeat(gate, attn.shape[-1] // gate.shape[-1], axis=-1)
        attn = (attn.astype(jnp.float32) * gate).astype(attn.dtype)
    with region("attn_out"):
        attn_delta = (_mm(cfg, attn, layer["attn"]["wo"], MODEL_AXIS, None)
                      + (layer["attn"]["bo"] if cfg.use_bias else 0))
    if cfg.parallel_block:
        x, aux = _ffn(cfg, layer, x)
        return _stream_write(x, attn_delta, None), pools, aux
    x, aux = _ffn(cfg, layer, _stream_write(x, attn_delta, mix))
    return x, pools, aux


def _kda_mix(cfg: TransformerConfig, layer, x, tail, valid, scan):
    """``_kda_mixer`` (the ``state_glue`` region: ``_period_body``), then
    the feed-forward part -> (x, aux, rows)."""
    y, rows = _kda_mixer(cfg, layer, x, tail, valid, scan)
    return (*_ffn(cfg, layer, _stream_write(x, y, None)), rows)


def _kda_mixer(cfg: TransformerConfig, layer, x, tail, valid, scan):
    """The linear-attention mixer on ``x [R, T, H]``: ``R`` rows of ``T``
    tokens each, ``tail [R, conv-1, 3*N]`` the rows of the q | k | v
    projections that precede them, ``valid [R, T]`` which tokens are real.
    ``scan(q, k, v, g, beta) -> o [R, T, NH, D]`` runs the recurrence (and
    keeps the state).  Returns (the mixer's output ``y``, the whole
    projection rows ``[R, conv-1+T, 3*N]`` for the caller to cut the next
    tail from).  A token that is not valid gets ``g = 0`` and
    ``beta = 0``, which leave the state as it was."""
    f32 = jnp.float32
    m = layer["kda"]
    R, T, _ = x.shape
    NH, D = cfg.kda_heads, cfg.kda_head_dim
    h = _ln1(cfg, layer, x)
    pre = jnp.concatenate([h @ m["wq"], h @ m["wk"], h @ m["wv"]], axis=-1)
    rows = jnp.concatenate([tail.astype(pre.dtype), pre], axis=1)
    conv = sum(rows[:, j:j + T].astype(f32) * m["conv"][j].astype(f32)
               for j in range(cfg.kda_conv))
    q, k, v = jnp.split(jax.nn.silu(conv).reshape(R, T, 3 * NH, D), 3, axis=2)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(D)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    rate = jnp.exp(m["a_log"].astype(f32))[:, None]
    g = -rate * jax.nn.softplus(
        ((h @ m["f_down"]) @ m["f_up"]).astype(f32).reshape(R, T, NH, D)
        + m["dt_bias"].astype(f32).reshape(NH, D))
    beta = 2.0 * jax.nn.sigmoid((h @ m["w_beta"]).astype(f32))
    g = jnp.where(valid[..., None, None], g, 0.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    o = scan(q, k, v, g, beta)  # float32 in, float32 out
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps) \
        * m["o_norm"].astype(f32)
    gate = jax.nn.sigmoid(((h @ m["g_down"]) @ m["g_up"]).astype(f32))
    return (o.reshape(R, T, NH * D) * gate).astype(x.dtype) @ m["wo"], rows


# ------------------------------------------------ SambaY (Phi-4-mini-flash)
def _ln1(cfg: TransformerConfig, layer, x):
    with region("norm"):
        return _norm(x, layer["norm1"]["scale"], layer["norm1"].get("bias"),
                     cfg.norm, cfg.norm_eps)


def _mamba_mix(cfg: TransformerConfig, layer, x, tail, scan):
    """The Mamba-1 mixer on ``x [R, T, H]`` (the ``state_glue`` region:
    ``_period_body``), then the feed-forward part: ``tail [R, conv-1,
    inner]`` the rows of ``u`` that
    precede the tokens, ``scan(dt, u, b, c, a, d) -> y [R, T, inner]``
    float32 runs the recurrence (and keeps the state).  Returns (x, aux, the
    scan's output ``y`` — the memory the gated memory units read —, the
    whole rows of ``u`` ``[R, conv-1+T, inner]`` for the caller to cut the
    next tail from)."""
    f32 = jnp.float32
    m = layer["mamba"]
    T, N, R = x.shape[1], cfg.ssm_state, cfg.ssm_dt_rank
    u, z = jnp.split(_ln1(cfg, layer, x) @ m["w_in"], 2, axis=-1)
    rows = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    u = jax.nn.silu(sum(
        rows[:, j:j + T].astype(f32) * m["conv"][j].astype(f32)
        for j in range(cfg.ssm_conv)) + m["conv_b"].astype(f32))
    delta, b, c = jnp.split(u.astype(x.dtype) @ m["w_x"], [R, R + N],
                            axis=-1)
    dt = jax.nn.softplus((delta @ m["w_dt"]).astype(f32)
                         + m["b_dt"].astype(f32))
    y = scan(dt, u, b, c, -jnp.exp(m["a_log"].astype(f32)), m["d"])
    out = (y * jax.nn.silu(z.astype(f32))).astype(x.dtype) @ m["w_out"]
    return (*_ffn(cfg, layer, _stream_write(x, out, None)), y, rows)


def _paired_q(q):
    """The differential form's queries for a kernel whose heads are pairs:
    ``[..., NH, D] -> [..., NH, 2D]``, an even head in the first half and an
    odd one in the second, zeros in the other — against a pair's keys ``[k1 |
    k2]`` an even head scores with ``k1`` and an odd one with ``k2``, and both
    read the pair's values ``[v1 | v2]``: keys and values stay as stored."""
    even = (jnp.arange(q.shape[-2]) % 2 == 0)[:, None]
    z = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(even, q, z), jnp.where(even, z, q)],
                           axis=-1)


def _diff_out(cfg: TransformerConfig, layer, x, o, i):
    """What follows the two softmaxes of a differential-attention layer:
    ``o [B, T, NH, 2D]`` holds ``A1`` of pair ``p`` at head ``2p`` and ``A2``
    at ``2p + 1``; ``W_o[(1 - lam0) RMSNorm(A1 - lam A2)] + b_o``, the
    residual and the feed-forward part -> (x, aux).  ``i``: the layer's index
    in the stack, which sets ``lam0``."""
    f32 = jnp.float32
    a = layer["attn"]
    B, T, NH, D2 = o.shape
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(i, f32))
    lam = (jnp.exp(jnp.sum(a["lam_q1"].astype(f32) * a["lam_k1"].astype(f32)))
           - jnp.exp(jnp.sum(a["lam_q2"].astype(f32)
                             * a["lam_k2"].astype(f32))) + lam0)
    o = o.astype(f32).reshape(B, T, NH // 2, 2, D2)
    d = o[..., 0, :] - lam * o[..., 1, :]
    d = (d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + cfg.norm_eps)
         * a["sub_norm"].astype(f32) * (1.0 - lam0))
    with region("attn_out"):
        delta = _mm(cfg, d.reshape(B, T, -1).astype(x.dtype), a["wo"],
                    MODEL_AXIS, None) + a["bo"]
    return _ffn(cfg, layer, _stream_write(x, delta, None))


def _pair_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """The heads as the paired kernels see them: half the K/V heads, twice
    as wide."""
    return dataclasses.replace(cfg, n_kv_heads=cfg.kv_heads // 2,
                               head_dim_override=2 * cfg.head_dim)


def _rows_attend(cfg: TransformerConfig, q, pools, l, table, positions,
                 active, use_kernel: bool, name: str):
    """One query a row, ``q [B, 1, NH, D]``, in the differential form over
    layer ``l`` of ``pools`` (``{"k", "v"}`` in pages) -> ``[B, 1, NH, 2D]``:
    the paged decode kernel under ``name``, or the gather path."""
    B = q.shape[0]
    q2, scale = _paired_q(q), 1.0 / math.sqrt(cfg.head_dim)
    if use_kernel:
        from ...ops.pallas.paged_attention import paged_decode_attention

        return paged_decode_attention(
            q2[:, 0], pools["k"], pools["v"], table, positions, layer=l,
            active=active, scale=scale, name=name)[:, None]
    S = table.shape[1] * pools["k"].shape[2]
    vis = (jnp.arange(S)[None] <= positions[:, None]) & active[:, None]
    o = _gather_window_attend(_pair_cfg(cfg), q2, pools, l, table,
                              positions[:, None], vis[:, None], scale=scale)
    return o.reshape(B, 1, q.shape[2], -1)


def _ring_pages(pools, ps: int):
    """The window layers' rings as pages, and back: ``win_k`` / ``win_v``
    ``[L, slots+1, window, F] <-> [L, (slots+1) * window/ps, ps, F]`` — slot
    ``s`` holds pages ``s * window/ps ...``; a reshape of the leading
    dimensions, so no data moves."""
    out = dict(pools)
    for name in ("win_k", "win_v"):
        if name in pools:
            a = pools[name]
            out[name] = a.reshape(a.shape[0], -1, ps, a.shape[-1])
    return out


def _ring_slots(pools, like):
    return {name: (a.reshape(like[name].shape) if name in ("win_k", "win_v")
                   else a) for name, a in pools.items()}


def _gmu_fn(cfg: TransformerConfig):
    def gmu_fn(layer, l, x, pools, cross, i):
        g = layer["gmu"]
        gate = jax.nn.silu((_ln1(cfg, layer, x) @ g["w_in"])
                           .astype(jnp.float32))
        y = (cross["mem"] * gate).astype(x.dtype) @ g["w_out"]
        x, aux = _ffn(cfg, layer, _stream_write(x, y, None))
        return x, pools, aux, cross
    return gmu_fn


def _xattn_fn(cfg: TransformerConfig, attend):
    """``attend(q [B, 1, NH, D]) -> [B, 1, NH, 2D]`` over the pages the
    full-attention layer wrote."""
    def xattn_fn(layer, l, x, pools, cross, i):
        a = layer["attn"]
        h = _ln1(cfg, layer, x)
        with region("attn_qkv"):
            q = _by_head(cfg, h, a["wq"], cfg.n_heads, cfg.head_dim, a["bq"])
        x, aux = _diff_out(cfg, layer, x, attend(q, pools), i)
        return x, pools, aux, cross
    return xattn_fn


# --------------------------------------------- latent attention (Mistral-4)
def _mla_project(cfg: TransformerConfig, layer, x, positions):
    """What a latent-attention layer makes of ``x [B, T, H]`` at ``positions
    [B, T]``: the queries' parts without and with rotary ``[B, T, NH, dn]`` /
    ``[B, T, NH, dr]`` (rotated), both already multiplied by the softmax
    scale ``a_t * (dn + dr) ** -0.5 * m ** 2`` in float32, and the row each
    token leaves in the pool ``[B, T, W]``: ``[RMSNorm(c_kv) | R_t(k_r) |
    zeros]``."""
    f32 = jnp.float32
    a = layer["attn"]
    NH, R = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    h = _ln1(cfg, layer, x)
    with region("attn_qkv"):
        cq = _norm(h @ a["w_dq"], a["q_norm"], None, "rmsnorm", cfg.norm_eps)
        q = _by_head(cfg, cq, a["w_uq"], NH, dn + dr)
        ckv = h @ a["w_dkv"]
        c = _norm(ckv[..., :R], a["kv_norm"], None, "rmsnorm", cfg.norm_eps)
        inv_freq = yarn_inv_freq(dr, cfg.rope_theta, cfg.rope_factor,
                                 cfg.rope_original_max, cfg.rope_beta_fast,
                                 cfg.rope_beta_slow)
        k_rope = rope_interleaved(ckv[..., None, R:], inv_freq, positions)[:, :, 0]
        q_rope = rope_interleaved(q[..., dn:], inv_freq, positions)
        m = 1.0
        if cfg.rope_mscale_all_dim and cfg.rope_factor > 1.0:
            m = 0.1 * cfg.rope_mscale_all_dim * math.log(cfg.rope_factor) + 1.0
        scale = jnp.full(positions.shape, m * m / math.sqrt(dn + dr), f32)
        if cfg.attn_scale_beta:
            scale = scale * (1.0 + cfg.attn_scale_beta * jnp.log1p(
                (positions // cfg.rope_original_max).astype(f32)))
        scale = scale[..., None, None]
        row = jnp.concatenate([c, k_rope], axis=-1)
        row = jnp.pad(row, ((0, 0), (0, 0),
                            (0, latent_width(cfg) - row.shape[-1])))
        return ((q[..., :dn].astype(f32) * scale).astype(x.dtype),
                (q_rope.astype(f32) * scale).astype(x.dtype), row)


def _mla_expanded(cfg: TransformerConfig, layer, q_nope, q_rope, rows, q_pos,
                  use_flash: bool, q_offset=None):
    """The expanded form: keys and values of every head made from cached rows
    ``rows [S, W]`` (slot index == position), ``[k_nope | v] = c W_ukv``, the
    shared rotary key beside each head's ``k_nope``; queries ``[1, T, NH,
    .]`` at positions ``q_pos [T]`` see the slots at or before them.  Returns
    ``[1, T, NH * dv]``.  The flash kernel takes one width for keys and
    values: where they differ the narrower is padded with zeros."""
    with region("latent_expand"):
        NH, R = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        S, T = rows.shape[0], q_nope.shape[1]
        kv = (rows[:, :R] @ layer["attn"]["w_ukv"]).reshape(S, NH, dn + dv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            rows[:, None, R:R + dr], (S, NH, dr))], axis=-1)[None]
        v = kv[..., dn:][None]
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        if use_flash:
            from ...ops.pallas.flash_attention import flash_attention

            wide = max(dn + dr, dv)
            widen = lambda a: jnp.pad(  # noqa: E731
                a, ((0, 0),) * 3 + ((0, wide - a.shape[-1]),))
            o = flash_attention(widen(q), widen(k), widen(v), causal=True,
                                q_offset=q_offset, sm_scale=1.0)[..., :dv]
        else:
            s = jnp.einsum("btnd,bsnd->bnts", q, k).astype(jnp.float32)
            vis = jnp.arange(S)[None, :] <= q_pos[:, None]
            p = jax.nn.softmax(jnp.where(vis[None, None], s, -1e30), axis=-1)
            o = jnp.einsum("bnts,bsnd->btnd", p.astype(v.dtype), v)
        return o.reshape(1, T, NH * dv)


def _mla_absorbed(cfg: TransformerConfig, layer, q_nope, q_rope, pools, l,
                  page_table, positions, active, use_kernel: bool):
    """The absorbed form, one query a row (``q_nope [B, NH, dn]``, ``q_rope
    [B, NH, dr]``): ``q~ = q_nope W_uk^T`` scores against the latent rows
    themselves, the output is ``(sum p c) W_uv``.  Returns ``[B, 1, NH *
    dv]``."""
    with region("latent_expand"):
        NH, R = cfg.n_heads, cfg.kv_lora_rank
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        B = q_nope.shape[0]
        w = layer["attn"]["w_ukv"].reshape(R, NH, -1)
        q = jnp.concatenate([jnp.einsum("bnd,rnd->bnr", q_nope, w[..., :dn]),
                             q_rope], axis=-1)
        if use_kernel:
            from ...ops.pallas.mla_attention import mla_decode_attention

            u = mla_decode_attention(q, pools["latent"], page_table, positions,
                                     l, active, rank=R)
        else:
            win = pools["latent"][l, page_table]
            win = win.reshape(B, -1, win.shape[-1])
            s = jnp.einsum("bnf,bsf->bns", q, win[..., :R + dr]
                           ).astype(jnp.float32)
            vis = (jnp.arange(win.shape[1])[None] <= positions[:, None]) \
                & active[:, None]
            p = jax.nn.softmax(jnp.where(vis[:, None], s, -1e30), axis=-1)
            u = jnp.einsum("bns,bsr->bnr", p.astype(q.dtype), win[..., :R])
        return jnp.einsum("bnr,rnv->bnv", u, w[..., dn:]).reshape(B, 1, -1)


# ------------------------------ grouped-query layers by type (MiMo-V2-Flash)
def _gqa_qkv(cfg: TransformerConfig, sh: GqaShape, layer, x, positions):
    """What a ``gqa_full`` or ``gqa_window`` layer makes of ``x [B, T, H]`` at
    ``positions [B, T]``: queries ``[B, T, the type's heads, k_dim]`` and keys
    ``[B, T, KVH, k_dim]``, the first ``rot`` lanes of each head rotated by
    the type's table (``GqaShape.rotate``), and values ``[B, T, KVH, v_dim]``
    times ``attn_value_scale`` — keys and values as the cache keeps them."""
    a = layer["attn"]
    h = _ln1(cfg, layer, x)
    with region("attn_qkv"):
        q, k, v = (_by_head(cfg, h, a[w], n, d)
                   for w, n, d in (("wq", sh.heads, sh.k_dim),
                                   ("wk", sh.kv_heads, sh.k_dim),
                                   ("wv", sh.kv_heads, sh.v_dim)))
        if cfg.attn_value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
        return sh.rotate(q, positions), sh.rotate(k, positions), v


def _gqa_softmax(q, k, v, vis, scale: float, sink=None):
    """The XLA form of both types: ``q [B, T, NH, dk]`` over ``k [B, S, KVH,
    dk]`` / ``v [B, S, KVH, dv]`` where ``vis [B, T, S]`` -> ``[B, T, NH *
    dv]``.  ``sink [NH]``: one more column of the softmax, dropped after it
    (it joins the maximum and the denominator and carries no value)."""
    g = q.shape[2] // k.shape[2]
    s = jnp.einsum("btnd,bsnd->bnts", q, _repeat_kv(k, g)
                   ).astype(jnp.float32) * scale
    s = jnp.where(vis[:, None], s, -1e30)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            (*s.shape[:3], 1))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)[..., :vis.shape[-1]]
    o = jnp.einsum("bnts,bsnd->btnd", p.astype(v.dtype), _repeat_kv(v, g))
    return o.reshape(*o.shape[:2], -1)


def _gqa_rows(sh: GqaShape, q, k_pool, v_pool, l, table, positions, active,
              sink, use_kernel: bool, name: str):
    """One query a row, ``q [B, NH, dk]``, over layer ``l`` of a pool in
    pages (the full layers' pages, or the window layers' rings as pages)
    whose key rows are stored split -> ``[B, NH * dv]``: the paged decode
    kernel under ``name``, or the gather path."""
    B, scale = q.shape[0], sh.scale
    if use_kernel:
        from ...ops.pallas.paged_attention import paged_decode_attention

        return paged_decode_attention(
            split_queries(q, sh.split, sh.kv_heads), k_pool, v_pool, table,
            positions, layer=l, active=active, scale=scale, name=name,
            rot=sh.split, sink=sink).reshape(B, -1)
    kk = merged_keys(k_pool[l, table].reshape(B, -1, sh.k_width), sh.split,
                     sh.kv_heads)
    vv = v_pool[l, table].reshape(B, -1, sh.kv_heads, sh.v_dim)
    vis = (jnp.arange(kk.shape[1])[None] <= positions[:, None]) \
        & active[:, None]
    return _gqa_softmax(q[:, None], kk.astype(q.dtype), vv.astype(q.dtype),
                        vis[:, None], scale, sink)[:, 0]


def _ring_read(pools, l, slot, W: int, ps: int, start, view):
    """A slot's rings of layer ``l`` before a chunk that starts at ``start``
    -> (where they lie, the rows as stored ``{"k" | "v": [W, F]}``, the same
    in position order through ``view`` — ring row ``r`` holds the last
    position before ``start`` that is ``r`` mod ``W``, so in order they are
    ``start - W .. start - 1`` — with zeros for those before 0, which were
    never written, and the first row of the ordered ring that was)."""
    at = (l, slot * (W // ps), 0, 0)
    order = (start + jnp.arange(W)) % W
    old = {nm: jax.lax.dynamic_slice(
        pools["win_" + nm], at,
        (1, W // ps, ps, pools["win_" + nm].shape[-1])).reshape(W, -1)
        for nm in ("k", "v")}
    k_first = jnp.maximum(W - start, 0)
    prev = {nm: view(jnp.where((jnp.arange(W) >= k_first)[:, None], a[order],
                               0)) for nm, a in old.items()}
    return at, old, prev, k_first


def _ring_write(pools, at, old, fresh, W: int, ps: int, start, n):
    """The rings after a chunk of ``n`` real tokens whose rows are ``fresh
    {"k" | "v": [1, C, ...]}``: row ``r`` takes the chunk's last real token at
    a position ``r`` mod ``W``, if the chunk has one."""
    last = start + n - 1
    p = last - (last - jnp.arange(W)) % W
    new = {nm: jnp.where(
        (p >= start)[:, None],
        a[0].reshape(a.shape[1], -1)[jnp.clip(p - start, 0, a.shape[1] - 1)],
        old[nm]) for nm, a in fresh.items()}
    return dict(pools, **{
        "win_" + nm: jax.lax.dynamic_update_slice(
            pools["win_" + nm], a.reshape(1, W // ps, ps, -1).astype(
                pools["win_" + nm].dtype), at) for nm, a in new.items()})


def _eva_decode_rows(cfg: TransformerConfig, page_table, positions, active,
                     ps: int, trash: int):
    """What the decode form of the ``eva`` mixer reads of a step's rows, from
    the host's table ``[B, sum_cap + window / ps]`` (``ragged.EvaRows``) and
    the rows' positions alone — so a row whose window closed at its last step
    attends ``window / chunk`` more summaries and an empty window with no
    other program: ``table`` ``[B, MP]`` the visible summary pages then the
    open pages (trash after them), ``attended`` ``[B]`` the rows a query sees
    (itself included), ``open_page`` the page its key and value go to and
    ``sum_page`` / ``sum_off`` where the summary of the chunk it ends goes
    (the trash page for a row that ends none, or is not active)."""
    from .ragged import EvaRows

    B, MP = page_table.shape
    W, C = cfg.eva_window, cfg.eva_chunk
    # the host's own arithmetic (it refuses a page that is not one chunk),
    # over the positions on the device
    ev = EvaRows(W, C, ps, (MP - W // ps) * ps * C)
    WP, sum_cap = ev.open_cap, ev.sum_cap
    with region("eva_glue"):
        rows = jnp.arange(B)
        vis_pages = ev.visible(positions) // ps
        i = jnp.arange(MP)[None]
        behind = i - vis_pages[:, None]  # index among the open pages
        src = jnp.where(behind < 0, i, jnp.minimum(sum_cap + behind, MP - 1))
        table = jnp.where(behind < WP,
                          jnp.take_along_axis(page_table, src, axis=1), trash)
        open_page = jnp.where(
            active, page_table[rows, sum_cap + positions % W // ps], trash)
        c = positions // C
        ends = active & (positions % C == C - 1)
        sum_page = jnp.where(
            ends, page_table[rows, jnp.minimum(c // ps, sum_cap - 1)], trash)
    return {"table": table, "attended": ev.rows_attended(positions),
            "open_page": open_page, "sum_page": sum_page, "sum_off": c % ps}


class _Forms(dict):
    """A program's ``layer_fns``: a mixer it has no form of is refused by
    name when a stack asks for it."""

    def __init__(self, program: str, **forms):
        super().__init__(forms)
        self.program = program

    def __missing__(self, mixer):
        raise NotImplementedError(
            f"{self.program} has no form of the {mixer!r} mixer: a model "
            "with recurrent state, a window cache or a latent cache is "
            "served through chunked prefill and the decode program only")


def paged_prefill(cfg: TransformerConfig, params, pools,
                  ids, page_rows, length) -> Tuple[jnp.ndarray, Any]:
    """Prefill one prompt.

    pools: {"k", "v"[, "k_scale", "v_scale"]} page pools (int8 codes +
    per-(page,slot,head) scales when KV quantization is on).
    ids: [S_pad] bucket-padded prompt; page_rows: [S_pad // page_size]
    page index per chunk (trash for pad chunks); length: real prompt length.
    Returns (last-token logits [V], pools).
    """
    S = ids.shape[0]
    ps = pools["k"].shape[2]
    with region("embed"):
        x = params["embed"]["tok"][ids][None]  # [1, S, H]
        if cfg.position == "learned":
            # the bucket may pad up to page_size-1 slots past the position
            # table; clamp explicitly (pad positions >= length never influence
            # real-token outputs under the causal mask)
            pos_idx = jnp.minimum(jnp.arange(S), params["embed"]["pos"].shape[0] - 1)
            x = x + params["embed"]["pos"][pos_idx][None]
        if "norm" in params["embed"]:  # bloom-style word_embeddings_layernorm
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm, cfg.norm_eps)
    positions = jnp.arange(S)[None]
    x = _streams_in(cfg, x)

    use_flash = _use_paged_kernel()

    def layer_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = attn_qkv(cfg, layer, read[0], positions)
        pools = _pool_write(
            pools, l, (page_rows,), k[0].reshape(S // ps, ps, *k.shape[2:]),
            v[0].reshape(S // ps, ps, *v.shape[2:]))
        if use_flash:
            # GQA-native flash kernel: no [S, S] score materialization.
            # Pad tokens past ``length`` see only earlier slots (causal)
            # and their outputs are discarded; real tokens see real slots.
            from ...ops.pallas.flash_attention import flash_attention

            attn = flash_attention(
                q, k, v, causal=True,
                alibi_slopes=(alibi_slopes(cfg.n_heads)
                              if cfg.position == "alibi" else None)
            ).reshape(1, S, -1)
        else:
            kk = _repeat_kv(k, cfg.n_heads // cfg.kv_heads)
            vv = _repeat_kv(v, cfg.n_heads // cfg.kv_heads)
            scores = jnp.einsum("btnd,bsnd->bnts", q, kk).astype(jnp.float32)
            scores = scores / math.sqrt(cfg.head_dim)
            if cfg.position == "alibi":
                scores = scores + _alibi_bias(cfg, jnp.arange(S),
                                              jnp.arange(S))
            causal = jnp.arange(S)[None, None, :, None] >= jnp.arange(S)[None, None, None, :]
            scores = jnp.where(causal, scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bnts,bsnd->btnd", probs, vv).reshape(1, S, -1)
        return _attn_out(cfg, layer, x, attn, pools, read)

    x, pools = _scan_layers(cfg, params, pools, x,
                            _Forms("whole-prompt prefill", attn=layer_fn))
    with region("head"):
        hidden = _norm(_streams_out(cfg, x[:, length - 1]),
                       params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
        logits = logits_fn(cfg, params, hidden[:, None])[0, 0]
    return logits, pools


def paged_copy_page(pools, src, dst):
    """Copy-on-write for the prefix cache: duplicate page ``src`` into
    ``dst`` across every page leaf (K/V codes and, under kv_quant, their
    scales, or a latent leaf — all laid out ``[L, P+1, ...]``; state slots
    and counters ride along untouched).  A sequence whose whole
    prompt is cached must write the KV of its final prompt token through
    the decode program; that write lands in its private copy so the
    shared cached page is never mutated.  The engine jits this with the
    pools donated — one compiled program regardless of src/dst."""
    return {name: (a.at[:, dst].set(a[:, src]) if name in PAGE_LEAVES else a)
            for name, a in pools.items()}


def pad_pages_pow2(pages, trash_page):
    """Pad a page list to the next power-of-two length with trash rows.
    The op-by-op gather/scatter path compiles one XLA program per row
    COUNT; the host KV tier's spill drains and restores batch arbitrary
    page counts every step, so bucketing keeps that a small fixed shape
    set (gathered trash content is discarded; scattered pad rows write
    zeros into the trash page, which every step clobbers anyway)."""
    n = 1
    while n < max(1, len(pages)):
        n *= 2
    return list(pages) + [trash_page] * (n - len(pages))


def paged_gather_pages(pools, pages, kv_heads):
    """Host copy of the given pool pages (KV export): one numpy array
    per pool leaf, K/V shaped ``[L, n_pages, page_size, KVH, D]`` (the
    pool's ``KVH*D`` split back on the host, where it is a view) in the
    pool's exact dtype (bf16 round-trips through ml_dtypes) — the
    device half of KV-page migration and of the host-RAM spill
    (``serving/kv_tier.py`` captures evicted prefix pages through
    exactly this layout, CRC-stamped by ``kv_transfer.page_crcs``)."""
    import numpy as np

    rows = jnp.asarray(np.asarray(pages, np.int32))
    out = {name: np.asarray(leaf[:, rows]) for name, leaf in pools.items()
           if name in PAGE_LEAVES}
    for name in ("k", "v"):
        if name in out:  # a latent pool's rows have no heads to split
            out[name] = out[name].reshape(*out[name].shape[:3], kv_heads, -1)
    return out


@jax.jit
def _pages_one_by_one(leaf, pages):
    # a loop of page-sized slices: the working set is the result, where a
    # gather of pages 128 KiB wide reserved 2.6 GB on the chip (PR 60)
    return jax.lax.map(lambda p: jax.lax.dynamic_index_in_dim(
        leaf, p, axis=1, keepdims=False), pages)


def paged_read_rows(pools, names, pages, trash_page):
    """Host copy of the rows of ``pages``, in that order, for each leaf of
    ``names``: ``[L, len(pages) * page_size, width]`` — a checking aid's read
    of wide pages beside an engine that nearly fills the device (the page
    count is padded to a power of two: few programs)."""
    import numpy as np

    rows = jnp.asarray(np.asarray(pad_pages_pow2(pages, trash_page), np.int32))
    out = {}
    for name in names:
        got = np.asarray(_pages_one_by_one(pools[name], rows))[:len(pages)]
        out[name] = got.transpose(1, 0, 2, 3).reshape(
            got.shape[1], -1, got.shape[-1])
    return out


def paged_scatter_pages(pools, pages, arrays):
    """Write host page arrays (``paged_gather_pages`` layout) into pool
    rows ``pages`` (KV import, and the H2D half of host-tier restore —
    one scatter path serves both).  Dtypes must match the pool exactly —
    a silent cast would break the bit-identical import contract.  Runs
    op-by-op outside jit (imports happen between steps, off the hot
    path); returns the updated pools dict."""
    import numpy as np

    paged = set(pools).intersection(PAGE_LEAVES)
    if set(arrays) != paged:
        raise ValueError(f"pool leaves {sorted(paged)} != bundle leaves "
                         f"{sorted(arrays)} (kv_quant mismatch?)")
    rows = jnp.asarray(np.asarray(pages, np.int32))
    out = dict(pools)
    for name in paged:
        leaf, src = pools[name], arrays[name]
        if jnp.dtype(leaf.dtype) != jnp.dtype(src.dtype):
            raise ValueError(f"pool leaf {name!r} dtype {leaf.dtype} != "
                             f"bundle dtype {src.dtype}: import must be "
                             "bit-identical, refusing to cast")
        out[name] = leaf.at[:, rows].set(
            jnp.asarray(src.reshape(*src.shape[:3], -1)
                        if name in ("k", "v") else src))
    return out


def paged_prefill_chunk(cfg: TransformerConfig, params, pools,
                        ids, chunk_rows, prev_table, start, n, slot=None,
                        final: bool = True) -> Tuple[jnp.ndarray, Any]:
    """Prefill ONE CHUNK of a prompt (FastGen Dynamic-SplitFuse-style
    chunked prefill, reference inference/v2 scheduler + blogs/deepspeed-
    fastgen): long prompts are processed in fixed-size chunks so decode
    steps for other sequences interleave between chunks, bounding
    per-step latency instead of stalling every running stream for a full
    prompt.

    This is also the engine's START-OFFSET prefill for automatic prefix
    caching: a request whose leading pages were mapped from the prefix
    cache prefills only the uncached suffix by calling this with
    ``start`` at the first uncached (page-aligned) position — the cached
    pages sit in ``prev_table`` at their position-ordered rows, so the
    ``< start`` visibility mask attends them exactly like
    previously-computed chunks.  The engine buckets the suffix length to
    the same power-of-two page counts as chunked prefill, keeping the
    suffix-only path a fixed set of compiled shapes.

    ids: [C] chunk tokens (C fixed, multiple of page_size);
    chunk_rows: [C // ps] pages receiving this chunk's K/V;
    prev_table: [MPb] the sequence's page-table prefix covering the
    window THROUGH this chunk (the kernel path reads the chunk's own
    keys from the pool; the caller buckets the length to power-of-two
    page counts so early chunks don't gather the full max window);
    start: global position of ids[0]; n: valid tokens; slot: the
    sequence's state slot (its decode row), for a model whose layers keep
    recurrent state — carried from chunk to chunk there.
    Chunk queries attend to all previously-written positions (< start,
    via the page pool) plus causally within the chunk.  Returns (logits
    of token start+n-1 — meaningful on the FINAL chunk — and pools).

    A stack with a cross-decoder (a ``dattn`` layer: Phi-4-mini-flash) runs
    the layers up to it and its K/V projection over the chunk, and — on a
    prompt's last chunk alone, ``final`` (static) — its attention, its
    feed-forward part and every layer after it for token ``start + n - 1``
    only; a chunk that is not final stops at the K/V write and returns zeros
    for logits.  ``prev_table`` is then the sequence's whole table row."""
    quant = "k_scale" in pools
    C = ids.shape[0]
    ps, _ = _page_geometry(pools)
    S_prev = prev_table.shape[0] * ps
    with region("embed"):
        x = params["embed"]["tok"][ids][None]  # [1, C, H]
        positions = start + jnp.arange(C)[None]
        if cfg.position == "learned":
            pos_idx = jnp.minimum(positions[0],
                                  params["embed"]["pos"].shape[0] - 1)
            x = x + params["embed"]["pos"][pos_idx][None]
        if "norm" in params["embed"]:
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm, cfg.norm_eps)
    x = _streams_in(cfg, x)

    with region("attn_glue"):
        # visibility of pooled (previous-chunk) slots: strictly before start
        prev_vis = jnp.arange(S_prev)[None, :] < start  # [1, S_prev]
        causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]  # [C(q), C(k)]
        blocked = {}
        if cfg.block_length:
            # generation by blocks: causal between blocks, bidirectional inside
            # one (``start`` and the chunk are whole blocks)
            blocked = {"block": cfg.block_length}
            causal = (jnp.arange(C)[:, None] | (cfg.block_length - 1)
                      ) >= jnp.arange(C)[None, :]

    # quant + chunked stays on the XLA path: the kernel window would put
    # the chunk's OWN keys through the int8 round-trip while the fallback
    # (and whole-prompt prefill) attend fresh in-chunk keys — keeping the
    # chunked/whole divergence limited to the inherent cross-chunk case
    use_kernel = _use_paged_kernel()
    use_flash = use_kernel and not quant

    def layer_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = attn_qkv(cfg, layer, read[0], positions)
        pools = _pool_write(
            pools, l, (chunk_rows,), k[0].reshape(C // ps, ps, *k.shape[2:]),
            v[0].reshape(C // ps, ps, *v.shape[2:]))
        kp, vp = _pool_window(pools, l, prev_table, cfg.kv_heads)
        if use_flash:
            # the table covers the window THROUGH this chunk (engine
            # buckets it to >= start + C), and pool-slot index == global
            # position — offset-flash's causal mask handles previous
            # chunks, in-chunk causality, AND trash/pad slots (they sit
            # at positions > every query) in one kernel, with no
            # [C, S_win] fp32 score materialization
            from ...ops.pallas.flash_attention import flash_attention

            attn = flash_attention(
                q, kp.astype(x.dtype)[None], vp.astype(x.dtype)[None],
                causal=True, q_offset=start,
                alibi_slopes=(alibi_slopes(cfg.n_heads)
                              if cfg.position == "alibi" else None),
                **blocked).reshape(1, C, -1)
            return _attn_out(cfg, layer, x, attn, pools, read)
        # keys = [previous pooled slots | this chunk]; the pooled half is
        # masked to < start, the chunk half causally within the chunk
        kk = jnp.concatenate([kp.astype(x.dtype)[None], k], axis=1)
        vv = jnp.concatenate([vp.astype(x.dtype)[None], v], axis=1)
        kk = _repeat_kv(kk, cfg.n_heads // cfg.kv_heads)
        vv = _repeat_kv(vv, cfg.n_heads // cfg.kv_heads)
        scores = jnp.einsum("btnd,bsnd->bnts", q, kk).astype(jnp.float32)
        scores = scores / math.sqrt(cfg.head_dim)
        if cfg.position == "alibi":
            # query i sits at global start+i; prev slots at their pool
            # index (page tables are position-ordered), chunk keys at
            # start+j
            scores = scores + _alibi_bias(
                cfg, start + jnp.arange(C),
                jnp.concatenate([jnp.arange(S_prev),
                                 start + jnp.arange(C)]))
        mask = jnp.concatenate(
            [jnp.broadcast_to(prev_vis, (C, S_prev)), causal], axis=1)
        scores = jnp.where(mask[None, None], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bnts,bsnd->btnd", probs, vv).reshape(1, C, -1)
        return _attn_out(cfg, layer, x, attn, pools, read)

    def mla_fn(layer, l, x, pools):
        # the chunk's rows go to the pool first; the window's rows, the
        # chunk's among them at their positions, are then expanded
        read = _stream_read(cfg, layer, x, "mixer")
        h = read[0]
        q_nope, q_rope, row = _mla_project(cfg, layer, h, positions)
        latent = pools["latent"].at[l, chunk_rows].set(
            row[0].reshape(C // ps, ps, -1).astype(pools["latent"].dtype))
        pools = dict(pools, latent=latent)
        rows = latent[l, prev_table].reshape(S_prev, -1).astype(x.dtype)
        attn = _mla_expanded(cfg, layer, q_nope, q_rope, rows, positions[0],
                             use_kernel, q_offset=start)
        return _attn_out(cfg, layer, x, attn, pools, read)

    def kda_fn(layer, l, x, pools):
        # the sequence's slot holds what its earlier chunks left; a chunk
        # that starts the sequence starts from nothing (a slot is never
        # zeroed by a program of its own)
        from ...ops.pallas.kda import kda_chunk, kda_chunk_xla

        keep = (start > 0).astype(jnp.float32)
        st0 = pools["kda_s"][l, slot] * keep
        tail = pools["kda_conv"][l, slot] * keep.astype(x.dtype)
        scan_fn = kda_chunk if use_kernel else kda_chunk_xla
        new = {}

        def scan(q, k, v, g, beta):
            o, new["s"] = scan_fn(q[0], k[0], v[0], g[0], beta[0], st0)
            return o[None]

        x, aux, rows = _kda_mix(cfg, layer, x, tail[None],
                                (jnp.arange(C) < n)[None], scan)
        # the rows of the last conv - 1 real tokens (reaching into the old
        # tail where the chunk holds fewer)
        tail = jax.lax.dynamic_slice_in_dim(rows[0], n, tail.shape[0], 0)
        return x, dict(pools,
                       kda_s=pools["kda_s"].at[l, slot].set(new["s"]),
                       kda_conv=pools["kda_conv"].at[l, slot].set(
                           tail.astype(pools["kda_conv"].dtype))), aux

    # ---- SambaY: state-space and window layers over the chunk, the cross-
    # decoder for the prompt's last token
    def mamba_fn(layer, l, x, pools, cross, i):
        from ...ops.pallas.ssm import ssm_chunk

        keep = start > 0
        st0 = jnp.where(keep, pools["ssm_s"][l, slot], 0.0)
        tail = jnp.where(keep, pools["ssm_conv"][l, slot], 0)

        new = {}

        def scan(dt, u, b, c, a, d):
            y, new["s"] = ssm_chunk(dt[0], u[0], b[0], c[0], a, d, st0, n,
                                    kernel=use_kernel)
            return y[None]

        x, aux, y, rows = _mamba_mix(cfg, layer, x, tail[None], scan)
        tail = jax.lax.dynamic_slice_in_dim(rows[0], n, tail.shape[0], 0)
        pools = dict(pools,
                     ssm_s=pools["ssm_s"].at[l, slot].set(new["s"]),
                     ssm_conv=pools["ssm_conv"].at[l, slot].set(
                         tail.astype(pools["ssm_conv"].dtype)))
        # the memory of the prompt's last token is all the cross-decoder
        # reads of this chunk
        return x, pools, aux, dict(cross, mem=jax.lax.dynamic_slice_in_dim(
            y, n - 1, 1, 1))

    def swa_fn(layer, l, x, pools, cross, i):
        W = cfg.sliding_window
        q, k, v = attn_qkv(cfg, layer, x, positions)
        pair = lambda a: a.reshape(*a.shape[:-2], a.shape[-2] // 2, -1)  # noqa: E731
        at, old, prev, k_first = _ring_read(
            pools, l, slot, W, ps, start,
            lambda a: a.reshape(1, W, cfg.kv_heads // 2, -1))
        kk = jnp.concatenate([prev["k"].astype(x.dtype), pair(k)], axis=1)
        vv = jnp.concatenate([prev["v"].astype(x.dtype), pair(v)], axis=1)
        q2, scale = _paired_q(q), 1.0 / math.sqrt(cfg.head_dim)
        if use_kernel:
            from ...ops.pallas.flash_attention import flash_attention

            o = flash_attention(q2, kk, vv, causal=True, q_offset=W,
                                sm_scale=scale, window=W, k_first=k_first)
        else:
            rows_ = W + jnp.arange(C)[:, None]
            cols = jnp.arange(W + C)[None]
            vis = (cols <= rows_) & (rows_ - cols < W) & (cols >= k_first)
            g = q2.shape[2] // kk.shape[2]
            sc = jnp.einsum("btnd,bsnd->bnts", q2, _repeat_kv(kk, g)
                            ).astype(jnp.float32) * scale
            pr = jax.nn.softmax(jnp.where(vis[None, None], sc, -1e30),
                                axis=-1).astype(x.dtype)
            o = jnp.einsum("bnts,bsnd->btnd", pr, _repeat_kv(vv, g))
        pools = _ring_write(pools, at, old, {"k": k, "v": v}, W, ps, start, n)
        x, aux = _diff_out(cfg, layer, x, o, i)
        return x, pools, aux, cross

    # ---- grouped-query layers by type: pages on the full layers, rings on
    # the window layers, keys stored split and rotated
    def gqa_full_fn(layer, l, x, pools):
        sh = gqa_shape(cfg, "gqa_full")
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = _gqa_qkv(cfg, sh, layer, read[0], positions)
        # the chunk's rows go to the pool first; the window's rows, the
        # chunk's among them, sit at their positions
        rows = {"k": split_keys(k[0], sh.split), "v": v[0].reshape(C, -1)}
        pools = dict(pools, **{
            nm: pools[nm].at[l, chunk_rows].set(
                r.reshape(C // ps, ps, -1).astype(pools[nm].dtype))
            for nm, r in rows.items()})
        kp = merged_keys(pools["k"][l, prev_table].reshape(S_prev, -1),
                         sh.split, sh.kv_heads)[None].astype(x.dtype)
        vp = pools["v"][l, prev_table].reshape(
            1, S_prev, sh.kv_heads, sh.v_dim).astype(x.dtype)
        if use_kernel:
            from ...ops.pallas.flash_attention import flash_attention

            o = flash_attention(q, kp, vp, causal=True, q_offset=start,
                                sm_scale=sh.scale).reshape(1, C, -1)
        else:
            vis = jnp.arange(S_prev)[None] <= positions[0][:, None]
            o = _gqa_softmax(q, kp, vp, vis[None], sh.scale)
        return _attn_out(cfg, layer, x, o, pools, read)

    def gqa_window_fn(layer, l, x, pools):
        sh = gqa_shape(cfg, "gqa_window")
        W, sink = sh.window, layer["attn"].get("sink")
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = _gqa_qkv(cfg, sh, layer, read[0], positions)
        at, old, prev, k_first = _ring_read(pools, l, slot, W, ps, start,
                                            lambda a: a[None])
        # [the ring in position order | the chunk] through the window mask
        kk = jnp.concatenate([merged_keys(prev["k"], sh.split, sh.kv_heads
                                          ).astype(x.dtype), k], axis=1)
        vv = jnp.concatenate([prev["v"].reshape(1, W, sh.kv_heads, sh.v_dim
                                                ).astype(x.dtype), v], axis=1)
        if use_kernel:
            from ...ops.pallas.flash_attention import flash_attention

            o = flash_attention(q, kk, vv, causal=True, q_offset=W,
                                sm_scale=sh.scale, window=W, k_first=k_first,
                                sink=sink).reshape(1, C, -1)
        else:
            rows_ = W + jnp.arange(C)[:, None]
            cols = jnp.arange(W + C)[None]
            vis = (cols <= rows_) & (rows_ - cols < W) & (cols >= k_first)
            o = _gqa_softmax(q, kk, vv, vis[None], sh.scale, sink)
        pools = _ring_write(pools, at, old,
                            {"k": split_keys(k, sh.split), "v": v},
                            W, ps, start, n)
        return _attn_out(cfg, layer, x, o, pools, read)

    # ---- EVA attention: [visible summaries | the open window | the chunk]
    def eva_fn(layer, l, x, pools):
        # ``chunk_rows`` = [the open pages that take the chunk's rows | the
        # summary pages that take its chunks' summaries]; ``prev_table`` the
        # visible summary pages then the open window's earlier pages,
        # right-aligned behind trash pages (``engine_v2._run_prefill_chunk``)
        W, Cc, NH = cfg.eva_window, cfg.eva_chunk, cfg.n_heads
        open_rows, sum_rows = chunk_rows[:C // ps], chunk_rows[C // ps:]
        q, k, v = attn_qkv(cfg, layer, x, positions)
        with region("eva_glue"):
            pools = _pool_write(
                pools, l, (open_rows,), k[0].reshape(C // ps, ps, *k.shape[2:]),
                v[0].reshape(C // ps, ps, *v.shape[2:]))
        ks, vs = eva_pool(layer["attn"],
                          k[0].reshape(C // Cc, Cc, *k.shape[2:]),
                          v[0].reshape(C // Cc, Cc, *v.shape[2:]))
        with region("eva_pool"):
            pools = _pool_write(
                pools, l, (sum_rows,), ks.reshape(-1, ps, *ks.shape[1:]),
                vs.reshape(-1, ps, *vs.shape[1:]))
        with region("eva_glue"):
            kp, vp = _pool_window(pools, l, prev_table, NH)
            kk = jnp.concatenate([kp.astype(x.dtype)[None], k], axis=1)
            vv = jnp.concatenate([vp.astype(x.dtype)[None], v], axis=1)
            # what stands before the chunk: the closed windows' summaries and
            # the open window's rows; the table's front is unused
            k_first = S_prev - (start // W) * (W // Cc) - start % W
            if use_kernel:
                from ...ops.pallas.flash_attention import flash_attention

                o = flash_attention(q, kk, vv, causal=True, q_offset=S_prev,
                                    window=S_prev + C, k_first=k_first)
            else:
                cols = jnp.arange(S_prev + C)[None]
                vis = (cols <= S_prev + jnp.arange(C)[:, None]) \
                    & (cols >= k_first)
                o = _gqa_softmax(q, kk, vv, vis[None],
                                 1.0 / math.sqrt(cfg.head_dim))
        return _attn_out(cfg, layer, x, o.reshape(1, C, -1), pools)

    last_pos = (start + n - 1).reshape(1)

    def attend_last(q, pools):
        return _rows_attend(cfg, q, pools, page_layers(cfg) - 1,
                            prev_table[None], last_pos,
                            jnp.ones((1,), bool), use_kernel,
                            "dstpu_paged_decode")

    def dattn_fn(layer, l, x, pools, cross, i):
        q, k, v = attn_qkv(cfg, layer, x, positions)
        pools = _pool_write(
            pools, l, (chunk_rows,), k[0].reshape(C // ps, ps, *k.shape[2:]),
            v[0].reshape(C // ps, ps, *v.shape[2:]))
        if not final:
            return x, pools, 0, cross
        # the cut: from here on, the prompt's last token alone
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, n - 1, 1, 1)  # noqa: E731
        x = cut(x)
        x, aux = _diff_out(cfg, layer, x, attend_last(cut(q), pools), i)
        return x, pools, aux, cross

    xdec = layers_of(cfg, "dattn") > 0
    like = pools
    x, pools = _scan_layers(
        cfg, params, _ring_pages(pools, ps), x,
        _Forms("chunked prefill", attn=layer_fn, mla=mla_fn, kda=kda_fn,
               mamba=mamba_fn, swa=swa_fn, dattn=dattn_fn, gmu=_gmu_fn(cfg),
               xattn=_xattn_fn(cfg, attend_last), gqa_full=gqa_full_fn,
               gqa_window=gqa_window_fn, eva=eva_fn),
        cross=({"mem": jnp.zeros((1, 1, cfg.ssm_inner), jnp.float32)}
               if cfg.ssm_inner else None),
        until=None if final or not xdec else "dattn")
    pools = _ring_slots(pools, like)
    if xdec and not final:
        return jnp.zeros((cfg.vocab_size,), x.dtype), pools
    if cfg.block_length:
        # prefill yields no token: the program ends at the last layer's K/V
        # write (no final norm, no head)
        return jnp.zeros((), x.dtype), pools
    with region("head"):
        hidden = _norm(_streams_out(cfg, x[:, 0 if xdec else n - 1]),
                       params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
        logits = logits_fn(cfg, params, hidden[:, None])[0, 0]
    return logits, pools


def _gather_window_attend(cfg: TransformerConfig, q, pools, l,
                          page_table, q_pos, vis, scale=None) -> jnp.ndarray:
    """[B, T] written-through queries attend the pooled pages via the
    XLA gather path — THE shared formulation of the paged_decode
    fallback (T=1) and paged_verify (T=k+1), so the dequant / GQA /
    alibi / mask / softmax chain cannot diverge between them.

    q: [B, T, NH, D]; pools, l: the carried pools and the layer to read;
    q_pos: [B, T] global positions; vis: [B, T, S] per-query visibility
    over pool slots.  Returns [B, T, NH*D]."""
    B, S = vis.shape[0], vis.shape[2]
    kk, vv = _pool_window(pools, l, page_table, cfg.kv_heads)  # [B,S,KVH,D]
    if "k_scale" in pools:
        kk = kk.astype(q.dtype)
        vv = vv.astype(q.dtype)
    kk = _repeat_kv(kk, cfg.n_heads // cfg.kv_heads)
    vv = _repeat_kv(vv, cfg.n_heads // cfg.kv_heads)
    scores = jnp.einsum("btnd,bsnd->bnts", q, kk).astype(jnp.float32)
    scores = (scores / math.sqrt(cfg.head_dim) if scale is None
              else scores * scale)
    if cfg.position == "alibi":
        scores = scores + _alibi_bias(cfg, q_pos, jnp.arange(S)[None])
    scores = jnp.where(vis[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnts,bsnd->btnd", probs, vv).reshape(
        B, q.shape[1], -1)


def paged_verify(cfg: TransformerConfig, params, pools,
                 ids, positions, page_table, active, n_valid
                 ) -> Tuple[jnp.ndarray, Any]:
    """Score a W-token window for every decode slot in ONE model call —
    the batched verify step of speculative decoding (engine_v2).

    This is ``paged_decode`` generalized from one pending token to a
    fixed-width window of ``W = k + 1`` tokens per sequence (the last
    accepted token followed by up to ``k`` draft tokens): each valid
    token's K/V is written into the sequence's pages exactly where plain
    decode would have written it, then every window query attends the
    pooled window ``slot_pos <= its position`` — the same
    write-then-gather data flow as decode, so position ``w``'s logits
    are what a plain decode step would have produced after consuming
    ``ids[:, :w+1]``.  The host accepts the longest draft prefix
    matching the per-position argmax and *rolls back* the pages of
    rejected tokens; rejected KV left inside kept pages is harmless —
    every read is masked to ``<= query position`` and the next window
    starts at the first rejected position, overwriting it before any
    query can see it.

    ids: [B, W] window tokens (ids[:, 0] = last accepted token);
    positions: [B] position of ids[:, 0]; page_table: [B, MP]
    (trash-filled); active: [B] bool; n_valid: [B] valid tokens per row
    (1..W — rows propose fewer than k drafts on an n-gram miss).
    Invalid/inactive tokens write to the trash page and their outputs
    are garbage the host never reads.  Returns (logits [B, W, V],
    pools).

    Like quantized chunked prefill this stays on the XLA gather path
    (the Pallas decode kernel is single-query; a multi-query window
    kernel is a future optimization) — the win measured here is model
    *invocations*, not attention FLOPs."""
    B, W = ids.shape
    ps = pools["k"].shape[2]
    trash = pools["k"].shape[1] - 1
    pos_w = positions[:, None] + jnp.arange(W)[None]  # [B, W]
    with region("embed"):
        x = params["embed"]["tok"][ids]  # [B, W, H]
        if cfg.position == "learned":
            pos_idx = jnp.minimum(pos_w, params["embed"]["pos"].shape[0] - 1)
            x = x + params["embed"]["pos"][pos_idx]
        if "norm" in params["embed"]:
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm, cfg.norm_eps)
    x = _streams_in(cfg, x)

    with region("attn_glue"):
        valid = active[:, None] & (jnp.arange(W)[None] < n_valid[:, None])
        S = page_table.shape[1] * ps
        page_idx = jnp.where(
            valid, page_table[jnp.arange(B)[:, None],
                              jnp.minimum(pos_w // ps, page_table.shape[1] - 1)],
            trash)
        off = pos_w % ps
        slot_pos = jnp.arange(S)[None, None]          # [1, 1, S]
        vis = slot_pos <= pos_w[:, :, None]           # [B, W, S]

    def layer_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = attn_qkv(cfg, layer, read[0], pos_w)  # [B, W, NH/KVH, D]
        pools = _pool_write(pools, l, (page_idx, off), k, v)
        attn = _gather_window_attend(cfg, q, pools, l, page_table, pos_w,
                                     vis)
        return _attn_out(cfg, layer, x, attn, pools, read)

    x, pools = _scan_layers(cfg, params, pools, x,
                            _Forms("speculative verify", attn=layer_fn))
    with region("head"):
        hidden = _norm(_streams_out(cfg, x), params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
        logits = logits_fn(cfg, params, hidden)  # [B, W, V]
    return logits, pools


def paged_decode(cfg: TransformerConfig, params, pools,
                 last_tokens, positions, page_table, active
                 ) -> Tuple[jnp.ndarray, Any]:
    """One token for every decode slot.

    pools: page pools dict (see paged_prefill).  last_tokens: [B];
    positions: [B] position of that token; page_table: [B, MP]
    (trash-filled beyond each sequence's pages); active: [B] bool.
    Returns (logits [B, V], pools).

    This is also the per-iteration body of :func:`paged_multi_decode` —
    ONE formulation, so the fused K-step scan cannot diverge from the
    single-step program it must be bit-identical to.
    """
    B = last_tokens.shape[0]
    ps, trash = _page_geometry(pools)
    with region("embed"):
        x = params["embed"]["tok"][last_tokens][:, None]  # [B, 1, H]
        if cfg.position == "learned":
            x = x + params["embed"]["pos"][positions][:, None]
        if "norm" in params["embed"]:
            x = _norm(x, params["embed"]["norm"]["scale"],
                      params["embed"]["norm"].get("bias"), cfg.norm, cfg.norm_eps)
    x = _streams_in(cfg, x)

    with region("attn_glue"):
        # clamp the page lookup for INACTIVE rows: inside the multi-step
        # scan a finished row's position stops advancing but may already sit
        # one past its last page; the gathered index is discarded (the
        # jnp.where routes the write to the trash page), active rows always
        # index in range by the engine's headroom-reservation contract
        page_idx = jnp.where(
            active,
            page_table[jnp.arange(B),
                       jnp.minimum(positions // ps, page_table.shape[1] - 1)],
            trash)
        off = positions % ps
        S = page_table.shape[1] * ps
        slot_pos = jnp.arange(S)[None]  # [1, S]
        vis = slot_pos <= positions[:, None]  # [B, S]

    use_kernel = _use_paged_kernel()

    def layer_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = attn_qkv(cfg, layer, read[0], positions[:, None])
        pools = _pool_write(pools, l, (page_idx, off), k[:, 0], v[:, 0])
        if use_kernel:
            # Pallas paged kernel: the whole pool goes in as it stands and
            # the kernel fetches each active row's live pages from it by
            # the scalar-prefetched layer and table — no [B, S, KVH, D]
            # materialization, nothing read for an inactive row (reference
            # ragged_ops decode kernels)
            from ...ops.pallas.paged_attention import paged_decode_attention

            attn = paged_decode_attention(
                q[:, 0], pools["k"], pools["v"], page_table, positions,
                k_scale=pools.get("k_scale"), v_scale=pools.get("v_scale"),
                alibi_slopes=(alibi_slopes(cfg.n_heads)
                              if cfg.position == "alibi" else None),
                layer=l, active=active).reshape(B, 1, -1)
        else:
            attn = _gather_window_attend(cfg, q, pools, l, page_table,
                                         positions[:, None],
                                         vis[:, None, :])
        return _attn_out(cfg, layer, x, attn, pools, read)

    def mla_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q_nope, q_rope, row = _mla_project(cfg, layer, read[0],
                                           positions[:, None])
        pools = dict(pools, latent=pools["latent"].at[l, page_idx, off].set(
            row[:, 0].astype(pools["latent"].dtype)))
        attn = _mla_absorbed(cfg, layer, q_nope[:, 0], q_rope[:, 0], pools, l,
                             page_table, positions, active, use_kernel)
        return _attn_out(cfg, layer, x, attn, pools, read)

    def kda_fn(layer, l, x, pools):
        # row b's state is slot b; the kernel visits the rows that decode, an
        # inactive row's conv tail (and XLA-form state) go to the trash slot
        from ...ops.pallas.kda import kda_step, kda_step_xla

        trash_slot = pools["kda_s"].shape[1] - 1
        dst = jnp.where(active, jnp.arange(B), trash_slot)
        new = {}

        def scan(q, k, v, g, beta):
            if use_kernel:
                o, new["s"] = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                       beta[:, 0], pools["kda_s"], l, active)
            else:
                o, st = kda_step_xla(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                     beta[:, 0], pools["kda_s"][l, :B])
                new["s"] = pools["kda_s"].at[l, dst].set(st)
            return o[:, None]

        x, aux, rows = _kda_mix(cfg, layer, x, pools["kda_conv"][l, :B],
                                jnp.ones((B, 1), bool), scan)
        return x, dict(pools, kda_s=new["s"],
                       kda_conv=pools["kda_conv"].at[l, dst].set(
                           rows[:, 1:].astype(pools["kda_conv"].dtype))), aux

    # ---- SambaY: every layer for one token a row
    def mamba_fn(layer, l, x, pools, cross, i):
        from ...ops.pallas.ssm import ssm_step

        trash_slot = pools["ssm_s"].shape[1] - 1
        dst = jnp.where(active, jnp.arange(B), trash_slot)
        new = {}

        def scan(dt, u, b, c, a, d):
            y, new["s"] = ssm_step(dt[:, 0], u[:, 0], b[:, 0], c[:, 0], a, d,
                                   pools["ssm_s"], l, active,
                                   kernel=use_kernel)
            return y[:, None]

        x, aux, y, rows = _mamba_mix(cfg, layer, x, pools["ssm_conv"][l, :B],
                                     scan)
        pools = dict(pools, ssm_s=new["s"],
                     ssm_conv=pools["ssm_conv"].at[l, dst].set(
                         rows[:, 1:].astype(pools["ssm_conv"].dtype)))
        return x, pools, aux, dict(cross, mem=y)

    def attend_pages(q, pools):
        return _rows_attend(cfg, q, pools, page_layers(cfg) - 1, page_table,
                            positions, active, use_kernel,
                            "dstpu_paged_decode")

    def dattn_fn(layer, l, x, pools, cross, i):
        q, k, v = attn_qkv(cfg, layer, x, positions[:, None])
        pools = _pool_write(pools, l, (page_idx, off), k[:, 0], v[:, 0])
        x, aux = _diff_out(cfg, layer, x, attend_pages(q, pools), i)
        return x, pools, aux, cross

    def swa_fn(layer, l, x, pools, cross, i):
        # position t lives at row t mod W of the row's ring, which the
        # kernel reads as W / ps pages of the slot; with no position
        # encoding the order of the keys is not needed
        W = cfg.sliding_window
        WP = W // ps
        slots = pools["win_k"].shape[1] // WP - 1
        q, k, v = attn_qkv(cfg, layer, x, positions[:, None])
        ring = positions % W
        win = _pool_write(
            {"k": pools["win_k"], "v": pools["win_v"]}, l,
            (jnp.where(active, jnp.arange(B), slots) * WP + ring // ps,
             ring % ps), k[:, 0], v[:, 0])
        o = _rows_attend(
            cfg, q, win, l, jnp.arange(B)[:, None] * WP + jnp.arange(WP)[None],
            jnp.minimum(positions, W - 1), active, use_kernel,
            "dstpu_window_decode")
        x, aux = _diff_out(cfg, layer, x, o, i)
        return x, dict(pools, win_k=win["k"], win_v=win["v"]), aux, cross

    def gqa_full_fn(layer, l, x, pools):
        sh = gqa_shape(cfg, "gqa_full")
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = _gqa_qkv(cfg, sh, layer, read[0], positions[:, None])
        pools = dict(
            pools,
            k=pools["k"].at[l, page_idx, off].set(
                split_keys(k[:, 0], sh.split).astype(pools["k"].dtype)),
            v=pools["v"].at[l, page_idx, off].set(
                v[:, 0].reshape(B, -1).astype(pools["v"].dtype)))
        o = _gqa_rows(sh, q[:, 0], pools["k"], pools["v"], l, page_table,
                      positions, active, None, use_kernel,
                      "dstpu_paged_decode")
        return _attn_out(cfg, layer, x, o[:, None], pools, read)

    def gqa_window_fn(layer, l, x, pools):
        # position t lives at row t mod W of the row's ring, which the
        # kernel reads as W / ps pages of the slot; the keys are stored
        # rotated, so their order is not needed
        sh = gqa_shape(cfg, "gqa_window")
        W = sh.window
        WP = W // ps
        slots = pools["win_k"].shape[1] // WP - 1
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = _gqa_qkv(cfg, sh, layer, read[0], positions[:, None])
        ring = positions % W
        at = (l, jnp.where(active, jnp.arange(B), slots) * WP + ring // ps,
              ring % ps)
        win_k = pools["win_k"].at[at].set(
            split_keys(k[:, 0], sh.split).astype(pools["win_k"].dtype))
        win_v = pools["win_v"].at[at].set(
            v[:, 0].reshape(B, -1).astype(pools["win_v"].dtype))
        o = _gqa_rows(
            sh, q[:, 0], win_k, win_v, l,
            jnp.arange(B)[:, None] * WP + jnp.arange(WP)[None],
            jnp.minimum(positions, W - 1), active,
            layer["attn"].get("sink"), use_kernel, "dstpu_window_decode")
        return _attn_out(cfg, layer, x, o[:, None],
                         dict(pools, win_k=win_k, win_v=win_v), read)

    # ---- EVA attention: a row, the page it completes pooled, and [visible
    # summary pages | open pages] composed from the row's position
    eva = _eva_decode_rows(cfg, page_table, positions, active, ps, trash) \
        if cfg.eva_window else None

    def eva_fn(layer, l, x, pools):
        q, k, v = attn_qkv(cfg, layer, x, positions[:, None])
        with region("eva_glue"):
            pools = _pool_write(pools, l, (eva["open_page"], off), k[:, 0],
                                v[:, 0])
            # the chunk this position may end is the page just written to
            page = [pools[nm][l, eva["open_page"]].reshape(B, ps, *k.shape[2:])
                    for nm in ("k", "v")]
        ks, vs = eva_pool(layer["attn"], *page)
        with region("eva_pool"):
            pools = _pool_write(pools, l, (eva["sum_page"], eva["sum_off"]),
                                ks, vs)
        with region("eva_glue"):
            if use_kernel:
                from ...ops.pallas.paged_attention import \
                    paged_decode_attention

                attn = paged_decode_attention(
                    q[:, 0], pools["k"], pools["v"], eva["table"],
                    eva["attended"] - 1, layer=l, active=active,
                    name="dstpu_eva_decode").reshape(B, 1, -1)
            else:
                attn = _gather_window_attend(
                    cfg, q, pools, l, eva["table"], positions[:, None],
                    (slot_pos < eva["attended"][:, None])[:, None, :])
        return _attn_out(cfg, layer, x, attn, pools)

    like = pools
    x, pools = _scan_layers(
        cfg, params, _ring_pages(pools, ps), x,
        _Forms("decode", eva=eva_fn, attn=layer_fn, mla=mla_fn, kda=kda_fn,
               mamba=mamba_fn, swa=swa_fn, dattn=dattn_fn, gmu=_gmu_fn(cfg),
               xattn=_xattn_fn(cfg, attend_pages), gqa_full=gqa_full_fn,
               gqa_window=gqa_window_fn),
        cross=({"mem": jnp.zeros((B, 1, cfg.ssm_inner), jnp.float32)}
               if cfg.ssm_inner else None))
    pools = _ring_slots(pools, like)
    with region("head"):
        hidden = _norm(_streams_out(cfg, x), params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
        logits = logits_fn(cfg, params, hidden)[:, 0]
    return logits, pools


def sample_tokens(logits, temps, key, sids, positions) -> jnp.ndarray:
    """On-device sampling shared by the single-step decode program and
    the fused multi-step scan: greedy argmax, or Gumbel-max categorical
    at temperature > 0.

    The sampling key is folded per **(request id, position)** — the
    engine passes each row's uid, a STABLE identity, and the position
    of the token being generated — never per dispatch and never per
    decode slot: a K-step fused scan draws exactly the noise K
    single-step dispatches would (sampled rows bit-identical across
    decode horizons, greedy trivially so), a preempted-and-readmitted
    or migrated sampled stream continues with ITS noise regardless of
    which slot it lands in, and co-batched requests at equal positions
    never share noise.  logits: [B, V]; temps: [B] (<= 0 = greedy);
    sids: [B] int32 per-row request ids; positions: [B] position the
    sampled token will occupy.  Returns [B] int32 token ids.
    """
    def _one(sid, p, zrow, t):
        k = jax.random.fold_in(jax.random.fold_in(key, sid), p)
        return jax.random.categorical(
            k, zrow / jnp.maximum(t, 1e-6)).astype(jnp.int32)

    with region("sample"):
        z = logits.astype(jnp.float32)
        greedy = jnp.argmax(z, axis=-1).astype(jnp.int32)
        sampled = jax.vmap(_one)(sids.astype(jnp.int32),
                                 positions.astype(jnp.int32), z, temps)
        return jnp.where(temps > 0.0, sampled, greedy)


def paged_block_pass(cfg: TransformerConfig, params, pools, ids, masked,
                     start, page_table, active, n_reveal
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """One pass of generation by diffusion over blocks, for every decode
    slot: a row's block of ``B = cfg.block_length`` positions ``start ...
    start + B - 1`` (``start`` a multiple of ``B``, which divides the page: a
    block never straddles one) runs against the kept K/V of every earlier
    block and its own fresh K/V, every one of its ``B`` queries attending all
    ``start + B`` positions.

    The block's K/V are written IN PLACE, to its slots of the row's page: a
    pass that is not the last is simply overwritten by the next, and the
    pass over a block with no masked position — the commit — is this same
    program, whose K/V are the ones kept.  No second cache for K/V in
    flight.

    ids: [R, B] the block's tokens (what a masked position holds is not
    read: it embeds ``cfg.mask_token_id``); masked: [R, B] bool; start: [R];
    page_table: [R, MP]; active: [R] bool (a row that is not active writes
    to the trash page and attends nothing); n_reveal: [R] masked positions
    to reveal.  Returns ``(ids, masked)`` after the reveal
    (``reveal_tokens``: the logits stay on the device) and the pools.

    The paged decode kernel serves as it is with the block folded into the
    head axis: a block's queries see the same pages at the same length, so
    they are ``B x G`` query rows of one K/V head."""
    R, Bk = ids.shape
    ps, trash = _page_geometry(pools)
    with region("embed"):
        tok = jnp.where(masked, jnp.int32(cfg.mask_token_id), ids)
        x = params["embed"]["tok"][tok]  # [R, B, H]
    x = _streams_in(cfg, x)
    with region("attn_glue"):
        pos = start[:, None] + jnp.arange(Bk)[None]  # [R, B]
        page_idx = jnp.where(
            active,
            page_table[jnp.arange(R),
                       jnp.minimum(start // ps, page_table.shape[1] - 1)],
            trash)
        at = (jnp.broadcast_to(page_idx[:, None], pos.shape), pos % ps)
        last = start + Bk - 1  # the block's last position: what every query sees
        S = page_table.shape[1] * ps
        vis = jnp.broadcast_to((jnp.arange(S)[None] <= last[:, None])[:, None],
                               (R, Bk, S))
    KVH, G = cfg.kv_heads, cfg.n_heads // cfg.kv_heads
    use_kernel = _use_paged_kernel()

    def layer_fn(layer, l, x, pools):
        read = _stream_read(cfg, layer, x, "mixer")
        q, k, v = attn_qkv(cfg, layer, read[0], pos)  # [R, B, NH | KVH, D]
        pools = _pool_write(pools, l, at, k, v)
        if use_kernel:
            from ...ops.pallas.paged_attention import paged_decode_attention

            fold = lambda a: a.reshape(R, Bk, KVH, G, -1).transpose(  # noqa: E731
                0, 2, 1, 3, 4)
            o = paged_decode_attention(
                fold(q).reshape(R, KVH * Bk * G, -1), pools["k"], pools["v"],
                page_table, last, layer=l, active=active)
            attn = o.reshape(R, KVH, Bk, G, -1).transpose(
                0, 2, 1, 3, 4).reshape(R, Bk, -1)
        else:
            attn = _gather_window_attend(cfg, q, pools, l, page_table, pos,
                                         vis)
        return _attn_out(cfg, layer, x, attn, pools, read)

    x, pools = _scan_layers(cfg, params, pools, x,
                            _Forms("the block pass", attn=layer_fn))
    with region("head"):
        hidden = _norm(_streams_out(cfg, x), params["final_norm"]["scale"],
                       params["final_norm"].get("bias"), cfg.norm,
                       cfg.norm_eps)
        logits = logits_fn(cfg, params, hidden)
    ids, masked = reveal_tokens(logits, ids, masked, n_reveal)
    return ids, masked, pools


def reveal_tokens(logits, ids, masked, n_reveal
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The reveal rule of a block pass, on the device (greedy, static
    low-confidence remasking): at each masked position the arg-max token of
    the logits AT that position (no shift) and its softmax probability as
    confidence; a row's ``n_reveal`` masked positions of highest confidence
    (the lower position on a tie) take their token and are never masked
    again.  logits: [R, B, V]; ids, masked: [R, B]; n_reveal: [R].  Returns
    the block's ``(ids, masked)`` after the pass — ``[R, B]`` integers are
    what crosses the link, never ``[R x B, V]`` logits."""
    with region("sample"):
        z = logits.astype(jnp.float32)
        top = jnp.argmax(z, axis=-1).astype(jnp.int32)
        # softmax(z)[argmax] = 1 / sum(exp(z - max))
        conf = 1.0 / jnp.sum(jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)),
                             axis=-1)
        conf = jnp.where(masked, conf, -1.0)
        i = jnp.arange(ids.shape[1])
        ahead = (conf[:, None, :] > conf[:, :, None]) | (
            (conf[:, None, :] == conf[:, :, None]) & (i[None, None, :]
                                                      < i[None, :, None]))
        reveal = masked & (jnp.sum(ahead, axis=-1) < n_reveal[:, None])
        return jnp.where(reveal, top, ids), masked & jnp.logical_not(reveal)


def paged_multi_decode(cfg: TransformerConfig, params, pools,
                       last_tokens, positions, page_table, active,
                       temps, eos_ids, budgets, sids, key, horizon: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """``horizon`` decode steps in ONE device program: a ``lax.scan``
    over the :func:`paged_decode` body (paged KV write → attention →
    on-device :func:`sample_tokens` with the per-position key fold →
    position/page-index advance), with per-row active/EOS/budget
    masking computed **in-scan** — finished rows write to the trash
    page and stop consuming pages.  ONE host pull per K tokens instead
    of K round-trips (engine_v2 ``_multi_decode``).

    last_tokens/positions/active/temps: as :func:`paged_decode`;
    page_table: [B, MP] covering each row's PRE-RESERVED horizon
    headroom (the engine reserves pages for ``budgets[b]`` tokens
    before dispatch — nothing allocates mid-scan); eos_ids: [B] int32
    (-1 = no EOS); budgets: [B] int32 tokens row ``b`` may emit this
    dispatch (min of the request's remaining max_new / model-window /
    deadline/headroom clamps and the horizon; 0 = inactive); sids: [B]
    int32 per-row request ids for the sampling fold.

    Returns ``(tokens [B, K] int32, produced [B] int32, pools)``:
    row ``b``'s emitted tokens are ``tokens[b, :produced[b]]``
    (positions past ``produced`` hold -1).  A row stops — and its
    later iterations write to the trash page — after its EOS token or
    its budget'th token, exactly where K single steps would have
    retired it; contract: the emitted stream is bit-identical to K
    single-step dispatches (greedy AND sampled — see sample_tokens).
    """
    B = last_tokens.shape[0]

    def step(carry, _):
        pools, last, pos, act, produced = carry
        logits, pools = paged_decode(cfg, params, pools, last, pos,
                                     page_table, act)
        tok = sample_tokens(logits, temps, key, sids, pos + 1)
        with region("sample"):  # (and what a row emits of it)
            emit = act
            tok = jnp.where(emit, tok, jnp.int32(-1))
            produced = produced + emit.astype(jnp.int32)
            eos_hit = emit & (eos_ids >= 0) & (tok == eos_ids)
            act = emit & jnp.logical_not(eos_hit) & (produced < budgets)
            last = jnp.where(emit, tok, last)
            pos = pos + emit.astype(jnp.int32)
        return (pools, last, pos, act, produced), tok

    act0 = active & (budgets > 0)
    carry0 = (pools, last_tokens, positions, act0,
              jnp.zeros((B,), jnp.int32))
    (pools, _l, _p, _a, produced), toks = jax.lax.scan(
        step, carry0, None, length=horizon)
    return jnp.transpose(toks), produced, pools
