"""Mixture-of-Experts: top-k gating + expert-parallel dispatch.

Reference parity: ``TopKGate`` (moe/sharded_moe.py:452), top-1/2/k gating
(:183/:290/:374) with capacity, load-balance aux loss and drop-tokens;
``MOELayer`` einsum dispatch (:536); expert-parallel all-to-all
(``_AllToAll``, :96).

TPU-native design: dispatch is expressed as dense einsums against a
[tokens, experts, capacity] one-hot — the same formulation the reference
uses on GPU — and the expert dimension of the stacked expert weights is
sharded over the "expert" mesh axis, so XLA lowers the dispatch/combine
einsums to the expert all-to-all over ICI (no hand-written _AllToAll).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..telemetry.regions import region


@dataclasses.dataclass
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    aux_loss_coef: float = 0.01
    z_loss_coef: float = 0.0
    drop_tokens: bool = True
    noisy_gate_policy: Optional[str] = None  # None | 'Jitter' | 'RSample'
    #: renormalize the kept top-k gate probs to sum 1 (reference
    #: normalize_gate_probabilities); qwen2-moe uses raw softmax values
    norm_topk: bool = True
    #: expert-parallel dispatch: "auto" takes the explicit-all-to-all
    #: shard_map path (ep_dispatch.py) whenever the topology has an expert
    #: axis > 1; "spmd" keeps the einsum/sort formulation and leaves the
    #: collectives to the SPMD partitioner
    ep_dispatch: str = "auto"
    #: dropless EP send-buffer capacity as a fraction of local assignments
    #: (None = exact worst case, guaranteed dropless; e.g. 2.0 = balanced
    #: load with 2x slack, overflow drops — see ep_dispatch.py)
    ep_send_capacity_factor: Optional[float] = None
    #: quantize the EP dispatch/return all-to-alls ("int8" | "fp8" | a
    #: CompressionSpec; None = full precision).  EQuARX reports all-to-all
    #: as the single biggest quantized-collective win; token payloads ride
    #: codes + block scales through comm/collectives, routing metadata
    #: stays exact (docs/COMM.md)
    ep_a2a_compression: Optional[Any] = None
    #: this chip's share of an expert-parallel deployment: it holds experts
    #: ``held_first .. held_first + held_count - 1`` of ``num_experts``
    #: (``held_count`` 0 = all of them).  The router keeps its
    #: ``num_experts`` outputs and ``top_k`` picks; the layer computes its
    #: own experts' part of the result (``moe_ffn_share``) and nothing
    #: stands in for the absent chips or their exchange
    held_first: int = 0
    held_count: int = 0
    #: how the router scores: "softmax" over all experts, or "sigmoid" per
    #: expert (each score on its own; picks by score + a per-expert
    #: selection bias where the layer has one, weights from the scores
    #: alone, renormalised over the picks with + 1e-6 when ``norm_topk``;
    #: no auxiliary loss, whatever ``aux_loss_coef`` says)
    scoring: str = "softmax"
    #: the picks' weights are multiplied by this (routed_scaling_factor)
    routed_scale: float = 1.0


#: the counters an expert share returns per call (``moe_ffn_share``), in
#: order: the engine's ``decode_stats()`` keys and the ``serve_step``
#: span's attributes
MOE_COUNTERS = ("moe_local_picks", "moe_experts_touched", "moe_padded_rows",
                "moe_layer_calls", "moe_grid_rows")

#: what a *trained* share returns per call after the picks on each of its
#: ``held_count`` experts: the rows of the blocks that hold picks (what each
#: of the three kernels ran), the rows of the worst-case buffer, 1 for the
#: call.  int32 ``[held_count + 3]``; the engine sums them over micro-batches
#: and steps (``engine.moe_stats()``)
MOE_TRAIN_COUNTERS = ("rows_run", "rows_grid", "calls")


def compute_capacity(tokens: int, cfg: MoEConfig, training: bool = True) -> int:
    factor = cfg.capacity_factor if training else cfg.eval_capacity_factor
    cap = int(tokens * factor * cfg.top_k / cfg.num_experts)
    return max(cap, cfg.min_capacity)


def top_k_gating(logits: jnp.ndarray, cfg: MoEConfig, capacity: int,
                 rng=None) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Compute dispatch/combine tensors.

    logits: [T, E].  Returns (combine [T, E, C], dispatch_mask [T, E, C] bool,
    aux_loss scalar).  Tokens beyond capacity are dropped (reference
    drop_tokens=True path).
    """
    T, E = logits.shape
    # gate probabilities, top-k routing and the load-balance aux are shared
    # with the dropless path (_gate_and_aux); this function adds only the
    # capacity/drop machinery
    gates, expert_idx, _, aux = _gate_and_aux(logits, cfg, rng)
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # [T, K, E]

    # position of each (token, k) within its expert's buffer: cumulative count
    # over tokens for that expert, k-major so k=0 assignments take priority
    flat = onehot.transpose(1, 0, 2).reshape(cfg.top_k * T, E)  # [K*T, E]
    pos_flat = jnp.cumsum(flat, axis=0) - flat  # slot index per assignment
    pos = pos_flat.reshape(cfg.top_k, T, E).transpose(1, 0, 2)  # [T, K, E]
    position = jnp.sum(pos * onehot, axis=-1)  # [T, K]
    keep = position < capacity  # dropped beyond capacity

    gate_k = jnp.take_along_axis(gates, expert_idx, axis=1)  # [T, K]
    gate_k = gate_k * keep.astype(gates.dtype)
    if cfg.norm_topk:
        # renormalize kept top-k gates (reference
        # normalize_gate_probabilities); norm_topk=False (qwen2-moe)
        # keeps the raw softmax values here too, matching the dropless path
        denom = jnp.sum(gate_k, axis=-1, keepdims=True)
        gate_k = gate_k / jnp.maximum(denom, 1e-9)

    cap_onehot = jax.nn.one_hot(position, capacity, dtype=jnp.float32)  # [T,K,C]
    # combine[t,e,c] = sum_k gate_k[t,k] * onehot[t,k,e] * cap_onehot[t,k,c]
    combine = jnp.einsum("tk,tke,tkc->tec", gate_k, onehot,
                         cap_onehot * keep[..., None].astype(jnp.float32))
    dispatch = combine > 0
    return combine, dispatch, aux


def _gate_and_aux(logits: jnp.ndarray, cfg: MoEConfig, rng=None, bias=None):
    """Shared top-k gate probabilities + load-balance aux (no capacity).
    ``bias`` ``[E]``: the sigmoid router's selection bias — it moves which
    experts are picked and never a pick's weight."""
    E = logits.shape[-1]
    if cfg.scoring == "sigmoid":
        gates = jax.nn.sigmoid(logits.astype(jnp.float32))
        select = gates if bias is None else gates + bias.astype(jnp.float32)
        _, expert_idx = jax.lax.top_k(select, cfg.top_k)
        gate_k = jnp.take_along_axis(gates, expert_idx, axis=1)
        if cfg.norm_topk:
            gate_k = gate_k / (jnp.sum(gate_k, -1, keepdims=True) + 1e-6)
        # no auxiliary loss: the bias is such a router's balancing
        return (gates, expert_idx, gate_k * cfg.routed_scale,
                jnp.asarray(0.0, jnp.float32))
    if cfg.scoring != "softmax":
        raise ValueError(f"unknown router scoring {cfg.scoring!r}")
    if cfg.noisy_gate_policy == "Jitter" and rng is not None:
        logits = logits * jax.random.uniform(rng, logits.shape, minval=0.98,
                                             maxval=1.02)
    elif cfg.noisy_gate_policy == "RSample" and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) / E
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, expert_idx = jax.lax.top_k(gates, cfg.top_k)  # [T, K]
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(onehot[:, 0, :], axis=0)
    aux = jnp.sum(me * ce) * E * cfg.aux_loss_coef
    if cfg.z_loss_coef > 0:
        aux = aux + cfg.z_loss_coef * jnp.mean(
            jnp.square(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)))
    gate_k = jnp.take_along_axis(gates, expert_idx, axis=1)  # [T, K]
    if cfg.norm_topk:
        gate_k = gate_k / jnp.maximum(jnp.sum(gate_k, -1, keepdims=True), 1e-9)
    if cfg.routed_scale != 1.0:  # (a softmax router without one: as it was)
        gate_k = gate_k * cfg.routed_scale
    return gates, expert_idx, gate_k, aux


def _expert_blocks(key: jnp.ndarray, n_experts: int, block_rows: int):
    """The padded buffer's layout from the picks' keys alone (``key`` values
    >= n_experts mark picks not computed here).  Returns (hit ``[E, N]`` bool:
    pick n is on expert e; counts, starts_raw, starts_b ``[E]``: each
    expert's picks, its first position among the sorted picks and its first
    buffer row; n_rows, block_expert, n_real as ``sort_pad_by_expert`` gives
    them).  The counts are a compare and a sum, not a scatter-add of N."""
    N = key.shape[0]
    hit = key[None, :] == jnp.arange(n_experts, dtype=key.dtype)[:, None]
    counts = jnp.sum(hit, axis=1, dtype=jnp.int32)
    starts_raw = jnp.cumsum(counts) - counts
    padded = ((counts + block_rows - 1) // block_rows) * block_rows
    starts_b = jnp.cumsum(padded) - padded
    most_touched = min(n_experts, N)
    n_rows = max(1, most_touched
                 + (N - most_touched) // block_rows) * block_rows
    block_starts = jnp.arange(n_rows // block_rows) * block_rows
    block_expert = jnp.clip(
        jnp.searchsorted(starts_b, block_starts, side="right") - 1,
        0, n_experts - 1).astype(jnp.int32)
    n_real = (jnp.sum(padded) // block_rows).astype(jnp.int32)
    return hit, counts, starts_raw, starts_b, n_rows, block_expert, n_real


def sort_pad_by_expert(key: jnp.ndarray, n_experts: int, block_rows: int):
    """Sort rows by expert key and compute block-padded destinations for the
    grouped matmul.  ``key`` values >= n_experts mark INVALID rows (they sort
    to the end and get dest == n_rows — scatter them with mode='drop').

    Returns (order, dest, n_rows, block_expert, n_real):
      order        [N] sorted row order (stable)
      dest         [N] padded-buffer row for each SORTED position
      n_rows       static padded buffer size: the most whole blocks N rows
                   can take (every expert one row into a block of its own,
                   the rest filling blocks)
      block_expert [n_rows/block_rows] expert of each row block
      n_real       int32 scalar: the blocks that hold rows,
                   ``sum(ceil(counts / block_rows))`` — they come first, and
                   ``dest`` points into them only
    """
    N = key.shape[0]
    _, _, starts_raw, starts_b, n_rows, block_expert, n_real = _expert_blocks(
        key, n_experts, block_rows)
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    se = jnp.clip(key_s, 0, n_experts - 1)
    dest = jnp.where(key_s < n_experts,
                     starts_b[se] + (jnp.arange(N) - starts_raw[se]), n_rows)
    return order, dest, n_rows, block_expert, n_real


def pick_row_maps(key: jnp.ndarray, top_k: int, n_experts: int,
                  block_rows: int):
    """The same layout as ``sort_pad_by_expert``'s, as the maps the dispatch
    and combine kernels walk (``ops/pallas/moe_dispatch.py``), built without
    a gather or a scatter of N index rows: XLA's cost by the index row, held
    or not.  Returns (row_pick ``[n_rows]``: the pick each buffer row holds —
    by arithmetic from the layout: row ``r`` of a block of expert ``e`` is
    sorted position ``starts_raw[e] + r - starts_b[e]``, a block's positions
    follow each other, so a block is one slice of the sort's order; n_valid
    ``[n_blocks]``: the rows of each block that hold a pick, 0 from ``n_real``
    on; dest ``[T, top_k]``: each pick's buffer row — its expert's first row
    plus its rank among the expert's picks, a cumulative sum — and -1 where
    the pick is not held; counts ``[E]``; n_rows, block_expert, n_real)."""
    N = key.shape[0]
    hit, counts, starts_raw, starts_b, n_rows, block_expert, n_real = \
        _expert_blocks(key, n_experts, block_rows)
    rank = jnp.cumsum(hit, axis=1, dtype=jnp.int32) - 1
    dest = jnp.sum(jnp.where(hit, starts_b[:, None] + rank, 0), axis=0,
                   dtype=jnp.int32)
    dest = jnp.where(key < n_experts, dest, -1).reshape(N // top_k, top_k)
    n_blocks = n_rows // block_rows
    into = jnp.arange(n_blocks) * block_rows - starts_b[block_expert]
    n_valid = jnp.where(jnp.arange(n_blocks) < n_real,
                        jnp.clip(counts[block_expert] - into, 0, block_rows),
                        0).astype(jnp.int32)
    order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                    (0, block_rows))
    first = jnp.clip(starts_raw[block_expert] + into, 0, N)
    row_pick = jax.vmap(
        lambda p: jax.lax.dynamic_slice(order, (p,), (block_rows,)))(first)
    return (row_pick.reshape(n_rows), n_valid, dest, counts, n_rows,
            block_expert, n_real)


def _expert_ffn_blocks(xs, experts, block_expert, n_real, activation,
                       block_rows, impl="auto"):
    """The three grouped matmuls of one FFN over sorted+padded tokens (the
    rows of the blocks past ``n_real`` come back undefined).  Where
    ``experts`` names an ``expert_first`` its matrices are those of several
    layers, stacked, and this layer's begin there: the blocks' map is moved
    by it, and the kernel fetches this layer's tiles out of the stack."""
    from ..ops.pallas.grouped_matmul import grouped_matmul

    if "expert_first" in experts:
        block_expert = block_expert + experts["expert_first"]

    def gm(a, w):
        return grouped_matmul(a, w, block_expert, block_rows, impl=impl,
                              n_real=n_real)

    with region("moe_glue"):  # (the grouped matmuls are kernels)
        if activation == "swiglu":
            h = jax.nn.silu(gm(xs, experts["w_gate"])) * gm(xs,
                                                            experts["w_up"])
        else:
            h = jax.nn.gelu(gm(xs, experts["w_up"]))
        return gm(h, experts["w_down"])


def _sorted_expert_ffn(xt, key, gate, top_k: int, n_experts: int, experts,
                       activation: str, block_rows: int, impl: str = "auto"):
    """The dropless tail: ``xt [T, H]`` tokens, ``key`` / ``gate``
    ``[T * top_k]`` each pick's expert (``>= n_experts``: not computed here)
    and weight.  Picks are sorted and padded by expert, run through the
    grouped matmuls and added back to their tokens.  ``impl="auto"`` moves
    the rows through the two row kernels on TPU (``dstpu_moe_dispatch``,
    ``dstpu_moe_combine``: the work of the picks held here; the rows
    ``rows_kernel_serves`` — float32 or bfloat16 in whole 128-lane tiles,
    so a hidden size of 2048, 3072 or 4096 alike) and through
    XLA's scatter and gathers on the CPU test tier (``"xla"``, the reference
    form: an invalid pick is scattered out of bounds and gathered as zero),
    as ``grouped_matmul``, which takes the same ``impl``, chooses its own.
    Returns (the tokens' sums, the picks on each expert, the rows of the
    blocks that hold picks — the rows each kernel runs, the rows of the
    worst-case buffer the grids span)."""
    from ..ops.pallas import moe_dispatch as rows

    if impl == "pallas" or (impl == "auto" and rows.on_tpu()
                            and rows.rows_kernel_serves(
                                xt.shape[1], xt.dtype, key.shape[0])):
        with region("moe_route"):
            (row_pick, n_valid, dest, counts, n_rows, block_expert,
             n_real) = pick_row_maps(key, top_k, n_experts, block_rows)
        maps = (row_pick, n_valid, n_real, dest, block_rows)
        with region("moe_glue"):  # (what XLA does round the row kernels)
            xs = rows.moe_dispatch(xt, *maps)
        ys = _expert_ffn_blocks(xs, experts, block_expert, n_real,
                                activation, block_rows, impl)
        with region("moe_glue"):
            out = rows.moe_combine(ys, gate.reshape(dest.shape), *maps)
        return out, counts, n_real * block_rows, n_rows
    with region("moe_route"):
        order, dest, n_rows, block_expert, n_real = sort_pad_by_expert(
            key, n_experts, block_rows)
        token_of = order // top_k
    with region("moe_glue"):
        xs = jnp.zeros((n_rows, xt.shape[1]), xt.dtype).at[dest].set(
            xt[token_of], mode="drop")
    ys = _expert_ffn_blocks(xs, experts, block_expert, n_real, activation,
                            block_rows, impl)
    with region("moe_glue"):
        contrib = (ys.at[dest].get(mode="fill", fill_value=0)
                   * gate[order][:, None].astype(ys.dtype))
        out = jnp.zeros_like(xt).at[token_of].add(contrib.astype(xt.dtype))
    with region("moe_route"):
        counts = jnp.bincount(jnp.minimum(key, n_experts),
                              length=n_experts + 1)[:n_experts]
    return out, counts, n_real * block_rows, n_rows


def moe_ffn_dropless(x: jnp.ndarray, gate_w: jnp.ndarray,
                     experts: Dict[str, jnp.ndarray], cfg: MoEConfig,
                     activation: str = "swiglu", rng=None,
                     block_rows: Optional[int] = None, router_bias=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """drop_tokens=False (reference top-k gating with drop_tokens=False /
    Megablocks dropless): NO token is ever dropped.  Tokens are sorted by
    expert and padded to block boundaries (a static worst-case buffer,
    ``sort_pad_by_expert``; ``block_rows`` None: ``expert_block_rows`` of
    the picks an expert expects), then the grouped Pallas matmul streams
    the block-diagonal expert FFNs of the blocks that hold tokens through
    the MXU.
    """
    from ..ops.pallas.grouped_matmul import expert_block_rows

    B, S, H = x.shape
    T = B * S
    E = cfg.num_experts
    K = cfg.top_k
    xt = x.reshape(T, H)

    with region("router"):
        logits = xt @ gate_w
        _, expert_idx, gate_k, aux = _gate_and_aux(logits, cfg, rng,
                                                   router_bias)

    out, _, _, _ = _sorted_expert_ffn(
        xt, expert_idx.reshape(T * K), gate_k.reshape(T * K), K, E, experts,
        activation, block_rows or expert_block_rows(T * K / E, x.dtype))
    return out.reshape(B, S, H), aux


def moe_ffn_share(x: jnp.ndarray, gate_w: jnp.ndarray,
                  experts: Dict[str, jnp.ndarray], cfg: MoEConfig,
                  activation: str = "swiglu",
                  block_rows: Optional[int] = None, router_bias=None,
                  training: bool = False
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One expert rank's part of the layer (``cfg.held_count`` experts from
    ``cfg.held_first``; ``experts`` holds only those).  Routes over all
    ``num_experts`` in float32, renormalises over the ``top_k`` picks, keeps
    the picks that land on a held expert, sorts and pads them by expert
    (blocks of ``expert_block_rows`` of the picks an expert expects, unless
    ``block_rows`` says) and runs the grouped matmul over the held experts.
    No pick is dropped; what the absent experts would add is left out (their
    chips add it).  Returns (the share's output, its counters: int32
    ``[len(MOE_COUNTERS)]`` — picks that landed on held experts, held experts
    touched, rows of the blocks that hold picks (the rows the grouped matmul
    ran), 1 for the call, rows of the worst-case buffer the kernel's grid
    spans).  Under ``training`` the same layer, differentiated through the
    kernels' backward, returns the counters a training step sums instead:
    the picks on each held expert, then ``MOE_TRAIN_COUNTERS``."""
    from ..ops.pallas.grouped_matmul import expert_block_rows

    B, S, H = x.shape
    T, K = B * S, cfg.top_k
    xt = x.reshape(T, H)
    with region("router"):
        logits = jnp.dot(xt.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        _, expert_idx, gate_k, _ = _gate_and_aux(logits, cfg,
                                                 bias=router_bias)
        local = expert_idx.reshape(T * K) - cfg.held_first
        held = (local >= 0) & (local < cfg.held_count)
        # a pick on an absent expert gets the invalid key
        key = jnp.where(held, local, cfg.held_count)
    out, counts, ran_rows, grid_rows = _sorted_expert_ffn(
        xt, key, gate_k.reshape(T * K), K, cfg.held_count, experts,
        activation,
        block_rows or expert_block_rows(T * K / cfg.num_experts, x.dtype))
    with region("moe_route"):  # (the counters)
        if training:
            stats = jnp.concatenate([counts, jnp.stack([
                ran_rows, jnp.full((), grid_rows, counts.dtype),
                jnp.ones((), counts.dtype)])]).astype(jnp.int32)
        else:
            stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                               ran_rows, jnp.ones((), counts.dtype),
                               jnp.full((), grid_rows, counts.dtype)]
                              ).astype(jnp.int32)
    return out.reshape(B, S, H), stats


def moe_ffn(x: jnp.ndarray, gate_w: jnp.ndarray, experts: Dict[str, jnp.ndarray],
            cfg: MoEConfig, activation: str = "swiglu", rng=None,
            training: bool = True, router_bias=None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """MoE feed-forward over [B, S, H] (reference MOELayer.forward).

    experts: stacked weights {w_gate/w_up: [E, H, F], w_down: [E, F, H]}
    (w_gate only for swiglu).  Returns (out [B, S, H], aux_loss); an expert
    share (``cfg.held_count``), which has no such loss, returns its
    counters there (``moe_ffn_share``).  ``router_bias`` ``[E]``: the
    selection bias of a sigmoid router (``MoEConfig.scoring``).
    """
    from .ep_dispatch import ep_dispatch_active, moe_ffn_ep

    if cfg.held_count:
        if cfg.drop_tokens:
            raise NotImplementedError(
                "an expert share (MoEConfig.held_count) is dropless, served "
                "and trained: there is no capacity form of it "
                "(moe_drop_tokens=True with a share)")
        # a share has no auxiliary loss: its counters take the slot
        return moe_ffn_share(x, gate_w, experts, cfg, activation,
                             router_bias=router_bias, training=training)
    if ep_dispatch_active(cfg):
        with region("moe_glue"):
            out = moe_ffn_ep(x, gate_w, experts, cfg, activation=activation,
                             rng=rng, training=training)
        if out is not None:
            return out
    if not cfg.drop_tokens:
        return moe_ffn_dropless(x, gate_w, experts, cfg, activation, rng,
                                router_bias=router_bias)
    if router_bias is not None:
        raise NotImplementedError(
            "a router selection bias has no capacity form: set "
            "moe_drop_tokens=False")
    B, S, H = x.shape
    T = B * S
    xt = x.reshape(T, H)
    capacity = compute_capacity(T, cfg, training)

    with region("router"):
        logits = xt @ gate_w  # [T, E] — gate in fp32 for stable routing
        combine, dispatch, aux = top_k_gating(logits, cfg, capacity, rng)

    # dispatch: [E, C, H] — expert dim sharded over the "expert" mesh axis in
    # the stacked weights drives XLA to all-to-all these buffers over ICI
    with region("moe_glue"):
        expert_in = jnp.einsum("tec,th->ech", dispatch.astype(x.dtype), xt)
        if activation == "swiglu":
            h = jax.nn.silu(jnp.einsum("ech,ehf->ecf", expert_in,
                                       experts["w_gate"]))
            h = h * jnp.einsum("ech,ehf->ecf", expert_in, experts["w_up"])
        else:
            h = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in,
                                       experts["w_up"]))
        expert_out = jnp.einsum("ecf,efh->ech", h, experts["w_down"])

        out = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
    return out.reshape(B, S, H), aux
