"""Expert-parallel MoE dispatch: shard_map over the expert axis with an
EXPLICIT all-to-all, so expert-weight gradients are born expert-sharded.

Reference parity: ``_AllToAll`` inside the expert-parallel group
(deepspeed/moe/sharded_moe.py:96) and its use by ``MOELayer.forward``
(:536) — each EP rank routes its local tokens, exchanges expert buffers
with the group, runs its LOCAL experts, and reverses the exchange.

Why this exists (vs leaving dispatch to SPMD, sharded_moe.py): under
EP + ZeRO-2/3 the backward of the SPMD dropless path produces
expert-weight grads in a token-sharded layout and XLA's SPMD partitioner
replicates them to reach the expert-sharded target ("involuntary full
rematerialization", a tracked SPMD scatter limitation).  Running the expert FFN inside ``shard_map`` over
the ``expert`` axis sidesteps the partitioner: each shard computes the
cotangent of ITS local expert slab only, so the grad is [E/ep, ...] by
construction and the wire traffic is exactly the two all-to-alls.

Layout contract (matches models/transformer.py partition rules):
  tokens   [B, S, H]   batch over (repl, data, expert), S over sequence
  w_gate/w_up [E, H, F] E over expert, F over model (TP)
  w_down   [E, F, H]    E over expert, F over model
The down-projection therefore psums over the model axis (Megatron-style
row-parallel combine).

Two paths, matching sharded_moe's two paths:
  capacity (drop_tokens=True)  — GShard einsum dispatch to [E, C, H],
    all-to-all over the E dim, local expert einsums on [E/ep, ep*C, H].
    Capacity is PER RANK (reference multi-rank semantics: each rank's
    gate computes positions over its local tokens only).
  dropless (drop_tokens=False) — assignments sorted by destination rank,
    packed into a [ep, C_send, H] buffer, all-to-all, receiver re-sorts
    by local expert and streams the Pallas grouped matmul, then the
    exchange is reversed.  C_send = T_loc*K guarantees NO token is ever
    dropped (the static worst case); ``ep_send_capacity_factor`` trades
    that guarantee for wire volume (C_send = A*factor/ep, overflow drops).

The aux (load-balance) loss is the pmean over token shards of the
per-shard aux — the reference's per-rank semantics (each rank computes
aux on its local batch; DP grad averaging means the effective loss is
the rank mean), not the global product-of-means the SPMD path computes.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..utils.jax_compat import shard_map

from ..parallel.mesh import (BATCH_AXES, EXPERT_AXIS, MODEL_AXIS, SEQ_AXIS,
                             peek_topology)

_TOKEN_AXES = tuple(BATCH_AXES) + (SEQ_AXIS,)


def _ep_a2a(x, a2a_spec):
    """The expert-group exchange: exact ``lax.all_to_all`` by default;
    with a compression spec, codes + block scales ride the wire through
    the shared layer (comm/collectives — EQuARX's headline verb).  The
    backward exchange stays exact (straight-through).

    Trailing dims are fused into one quantized dim per destination rank:
    quantizing raw H rows would pad each to a whole codec block (an H=16
    row would INFLATE to 128 codes); fused, the pad is amortized over the
    entire per-rank payload and blocks simply span token boundaries."""
    if a2a_spec is None:
        return jax.lax.all_to_all(x, EXPERT_AXIS, 0, 0)
    from ..comm.collectives import compressed as _cc

    flat = x.reshape(x.shape[0], -1)
    out = _cc.all_to_all(flat, EXPERT_AXIS, a2a_spec, 0, 0, False)
    return out.reshape(x.shape)


def _inside_manual_axes() -> bool:
    """True when tracing inside shard_map/pmap (named axes bound) — the EP
    shard_map cannot nest there (e.g. under the pipeline's manual map).
    Reads a private jax API (the pinned 0.9.0 has it); if it moves, this
    import fails loudly rather than switching MoE dispatch to SPMD."""
    from jax._src.core import get_axis_env

    return bool(get_axis_env().axis_sizes)


def ep_dispatch_active(cfg) -> bool:
    """Whether moe_ffn should take the explicit-all-to-all EP path."""
    if getattr(cfg, "ep_dispatch", "auto") == "spmd":
        return False
    topo = peek_topology()
    if topo is None:
        return False
    ep = topo.expert_parallel_size
    if ep <= 1 or cfg.num_experts % ep != 0:
        return False
    if _inside_manual_axes():
        return False
    return True


def _pmean_aux(aux):
    return jax.lax.pmean(aux, _TOKEN_AXES)


def _fold_rng(rng):
    """Per-shard independent gate noise: fold each token-axis index in."""
    if rng is None:
        return None
    for ax in _TOKEN_AXES:
        rng = jax.random.fold_in(rng, jax.lax.axis_index(ax))
    return rng


def _expert_einsums(ein, wg, wu, wd, activation):
    """The three expert einsums on [E_loc, c, H] with model-TP combine."""
    if activation == "swiglu":
        h = jax.nn.silu(jnp.einsum("ech,ehf->ecf", ein, wg))
        h = h * jnp.einsum("ech,ehf->ecf", ein, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", ein, wu))
    out = jnp.einsum("ecf,efh->ech", h, wd)
    return jax.lax.psum(out, MODEL_AXIS)


def _capacity_block(x, gate_w, wg, wu, wd, rng, *, cfg, activation, ep,
                    training, a2a_spec=None):
    """Per-EP-rank capacity dispatch (reference MOELayer + _AllToAll)."""
    from .sharded_moe import compute_capacity, top_k_gating

    Bl, Sl, H = x.shape
    T = Bl * Sl
    E = cfg.num_experts
    E_loc = E // ep
    xt = x.reshape(T, H)
    cap = compute_capacity(T, cfg, training)  # per-rank, local tokens

    logits = xt @ gate_w
    combine, dispatch, aux = top_k_gating(logits, cfg, cap, _fold_rng(rng))
    aux = _pmean_aux(aux)

    expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
    # dispatch A2A: split the expert dim over ranks, concat source dim
    send = expert_in.reshape(ep, E_loc, cap, H)
    recv = _ep_a2a(send, a2a_spec)  # [ep(src), E_loc, C, H]
    ein = recv.transpose(1, 0, 2, 3).reshape(E_loc, ep * cap, H)

    eout = _expert_einsums(ein, wg, wu, wd, activation)

    back = eout.reshape(E_loc, ep, cap, H).transpose(1, 0, 2, 3)
    ret = _ep_a2a(back, a2a_spec).reshape(E, cap, H)
    out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), ret)
    return out.reshape(Bl, Sl, H), aux


def _dropless_block(x, gate_w, wg, wu, wd, rng, *, cfg, activation, ep,
                    block_rows, c_send, a2a_spec=None):
    """Per-EP-rank dropless dispatch: sort by destination rank, A2A,
    receiver sorts by local expert and runs the grouped Pallas matmul."""
    from .sharded_moe import (_expert_ffn_blocks, _gate_and_aux,
                              sort_pad_by_expert)

    Bl, Sl, H = x.shape
    T = Bl * Sl
    E = cfg.num_experts
    K = cfg.top_k
    E_loc = E // ep
    A = T * K
    xt = x.reshape(T, H)

    logits = xt @ gate_w
    _, expert_idx, gate_k, aux = _gate_and_aux(logits, cfg, _fold_rng(rng))
    aux = _pmean_aux(aux)

    flat_e = expert_idx.reshape(A)
    flat_g = gate_k.reshape(A)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    token_of = order // K
    dest_rank = sorted_e // E_loc

    counts_r = jnp.bincount(flat_e, length=E).reshape(ep, E_loc).sum(-1)
    starts_r = jnp.cumsum(counts_r) - counts_r
    rank_pos = jnp.arange(A) - starts_r[dest_rank]
    keep = rank_pos < c_send  # always true when c_send == A (dropless)

    send_x = jnp.zeros((ep, c_send, H), x.dtype).at[dest_rank, rank_pos].set(
        xt[token_of], mode="drop")
    send_le = jnp.full((ep, c_send), -1, jnp.int32).at[dest_rank, rank_pos].set(
        (sorted_e % E_loc).astype(jnp.int32), mode="drop")
    recv_x = _ep_a2a(send_x, a2a_spec)
    recv_le = jax.lax.all_to_all(send_le, EXPERT_AXIS, 0, 0)  # routing: exact

    # receiver: re-sort the ep*c_send rows by local expert (invalid -> end)
    R = ep * c_send
    rl = recv_le.reshape(R)
    key = jnp.where(rl >= 0, rl, E_loc)  # E_loc = the invalid sentinel
    order2, dest, n_rows, block_expert, n_real = sort_pad_by_expert(
        key, E_loc, block_rows)
    xs = jnp.zeros((n_rows, H), x.dtype).at[dest].set(
        recv_x.reshape(R, H)[order2], mode="drop")

    experts_loc = {"w_up": wu, "w_down": wd}
    if activation == "swiglu":
        experts_loc["w_gate"] = wg
    ys = _expert_ffn_blocks(xs, experts_loc, block_expert, n_real,
                            activation, block_rows)
    # (rows of the blocks past n_real are undefined: gather, then combine)
    y_rows = jnp.zeros((R, H), ys.dtype).at[order2].set(
        ys.at[dest].get(mode="fill", fill_value=0))
    y_rows = jax.lax.psum(y_rows, MODEL_AXIS)  # model-TP down-proj combine
    ret = _ep_a2a(y_rows.reshape(ep, c_send, H), a2a_spec)
    y_asgn = ret.at[dest_rank, rank_pos].get(mode="fill", fill_value=0)
    contrib = y_asgn * (flat_g[order] * keep)[:, None].astype(ys.dtype)
    out = jnp.zeros((T, H), x.dtype).at[token_of].add(contrib.astype(x.dtype))
    return out.reshape(Bl, Sl, H), aux


def moe_ffn_ep(x: jnp.ndarray, gate_w: jnp.ndarray,
               experts: Dict[str, jnp.ndarray], cfg, activation: str = "swiglu",
               rng=None, training: bool = True,
               block_rows: Optional[int] = None
               ) -> Optional[Tuple[jnp.ndarray, jnp.ndarray]]:
    """MoE FFN through the explicit EP all-to-all.  Returns None when the
    global batch/seq do not divide the token-shard grid (caller falls back
    to the SPMD path — jit would reject those shardings anyway).
    ``block_rows`` None: ``expert_block_rows`` of the picks a rank's expert
    expects under balanced load (the dropless receiver's row blocks)."""
    from ..ops.pallas.grouped_matmul import expert_block_rows

    topo = peek_topology()
    mesh = topo.mesh
    ep = topo.expert_parallel_size
    B, S, H = x.shape
    bs_shards = topo.dp_world_size
    seq_shards = topo.seq_parallel_size
    if B % bs_shards or S % seq_shards:
        return None
    T_loc = (B // bs_shards) * (S // seq_shards)

    wg = experts.get("w_gate") if activation == "swiglu" else None
    wu, wd = experts["w_up"], experts["w_down"]
    if wu.shape[-1] % topo.model_parallel_size:
        # the FFN dim cannot split evenly over the model axis; GSPMD's
        # uneven-sharding support handles this — fall back to SPMD
        return None

    if rng is None and cfg.noisy_gate_policy:
        # rng=None means NO gate noise (sharded_moe semantics); clear the
        # policy before the blocks bind cfg, or the dummy key would jitter
        cfg = dataclasses.replace(cfg, noisy_gate_policy=None)

    from ..comm.collectives import CompressionSpec

    a2a_spec = CompressionSpec.parse(
        getattr(cfg, "ep_a2a_compression", None))

    if cfg.drop_tokens:
        block = partial(_capacity_block, cfg=cfg, activation=activation,
                        ep=ep, training=training, a2a_spec=a2a_spec)
    else:
        A = T_loc * cfg.top_k
        factor = getattr(cfg, "ep_send_capacity_factor", None)
        if factor is None:
            c_send = A  # static worst case: guaranteed dropless
        else:
            c_send = min(A, -(-math.ceil(A * factor / ep) // 8) * 8)
        block = partial(_dropless_block, cfg=cfg, activation=activation,
                        ep=ep, c_send=c_send,
                        block_rows=block_rows or expert_block_rows(
                            A * ep / cfg.num_experts, x.dtype),
                        a2a_spec=a2a_spec)

    rng_in = rng if rng is not None else jax.random.PRNGKey(0)

    tok_spec = P(tuple(BATCH_AXES), SEQ_AXIS, None)
    w_col = P(EXPERT_AXIS, None, MODEL_AXIS)  # w_gate / w_up [E, H, F]
    in_specs = (tok_spec, P(None, None),
                w_col if wg is not None else P(),
                w_col, P(EXPERT_AXIS, MODEL_AXIS, None), P())
    mapped = shard_map(
        block, mesh=mesh, in_specs=in_specs,
        out_specs=(tok_spec, P()), check_vma=False)
    # non-swiglu blocks never read wg; a dummy scalar rides the P() spec
    wg_in = wg if wg is not None else jnp.zeros((), x.dtype)
    return mapped(x, gate_w, wg_in, wu, wd, rng_in)
