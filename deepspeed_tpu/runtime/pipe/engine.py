"""Pipeline parallelism.

Reference: ``PipelineModule`` partitions a LayerSpec list across stages and
``PipelineEngine`` executes a 1F1B instruction schedule with p2p send/recv
(``runtime/pipe/engine.py:60``, ``schedule.py:189``, ``p2p.py``).

TPU-native design: the pipeline is ONE SPMD program.  Layer parameters are
stacked [L, ...] with the leading dim sharded over the "pipe" mesh axis
(each stage holds L/P layers); a ``shard_map`` body runs the classic
pipelined loop — at step t every stage applies its layers to its current
micro-batch activation and ``ppermute``s the result to the next stage.
``lax.scan`` over the T = M + P - 1 steps makes the whole schedule
differentiable: the backward pass is the reversed pipeline (the 1F1B
backward wave), with per-stage remat bounding activation memory.

Composition: pairs with DP (batch dim sharded over data axes inside the
same shard_map) and ZeRO-1 optimizer sharding outside — the same pairing
the reference uses (bf16+ZeRO-1 with PP, runtime/bf16_optimizer.py).
Embedding / final-norm / LM-head weights are replicated across pipe and
applied at the boundary stages.

Perf citizenship (docs/PIPELINE.md):

* **Compressed activation hops** — with ``pipeline.hop_compression`` the
  per-tick ``ppermute`` (and its backward-wave transpose) rides the
  quantized collective verbs (``comm/collectives/compressed.py``):
  int8/fp8 codes + block scales on the wire both directions.  Error
  feedback on the backward hop carries per-tick residuals through the
  ``_pipe_comm["e"]`` scan-xs channel into
  ``TrainState.comm_errors["pipe"]`` (the PR-15 lifecycle contract:
  donated with the step, checkpointed by path key, kept-not-poisoned on
  overflow steps).
* **Bubble-overlapped grad reduce** — a ``PipeOverlapPlan``
  (``runtime/pipe/overlap.py``) hooks each tick's stage apply with a
  ``custom_vjp`` whose backward reduces that tick's per-stage layer
  gradient over the data axis IN the scan (drain-tick bubbles are free
  comm time), delivering the reduced payload through the
  ``_pipe_comm["g"]`` gslot cotangent channel; the layer leaves are
  ``stop_gradient``-ed so the shard_map boundary emits no monolithic fp
  psum for them.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...models.transformer import (TransformerConfig, _block, _norm,
                                   _pick_attn, init_transformer_params,
                                   transformer_partition_rules)
from ...parallel.mesh import BATCH_AXES, PIPE_AXIS, get_topology
from ...utils.jax_compat import shard_map
from ...runtime.module import ModelSpec


def pipeline_partition_rules(cfg: TransformerConfig):
    """Transformer rules with the stacked-layer dim sharded over 'pipe'."""
    rules = []
    for pattern, spec in transformer_partition_rules(cfg):
        entries = list(spec)
        if pattern.startswith(r"mlp/") or pattern.startswith(r"attn/") or \
                "norm1" in pattern or "norm2" in pattern:
            entries[0] = PIPE_AXIS
        if pattern.startswith("layers/"):
            entries[0] = PIPE_AXIS
        rules.append((pattern, P(*entries)))
    # norms inside layers aren't in the base rules (they default replicated);
    # add explicit pipe-sharded rules for every stacked layer tensor
    rules.insert(0, (r"layers/.*norm", P(PIPE_AXIS, None)))
    rules.insert(0, (r"layers/attn/b[qkvo]$", P(PIPE_AXIS, None)))
    rules.insert(0, (r"layers/mlp/b_(up|down)$", P(PIPE_AXIS, None)))
    out = []
    for pattern, spec in rules:
        if pattern.startswith(("attn/", "mlp/")):
            pattern = "layers/" + pattern
        out.append((pattern, spec))
    return out


def _stage_apply(cfg: TransformerConfig, local_layers, x, positions, attn_fn):
    """Apply this stage's L/P layers (inner scan)."""

    def body(carry, layer):
        y, _aux = _block(cfg, carry, layer, positions, None, attn_fn)
        return y, _aux

    block = jax.checkpoint(body) if cfg.remat else body
    x, auxs = jax.lax.scan(block, x, local_layers)
    return x, jnp.sum(auxs)


def _pipe_body(params, ids, labels, stage_arr, pipe_comm, *,
               cfg: TransformerConfig, num_micro: int, pp: int):
    """shard_map body.  ids/labels: local [b, S] batch shard; params: local
    slices (layers: [L/pp, ...], embed/head: replicated); stage_arr: local
    [1] slice of a pipe-sharded iota — the stage id (``axis_index`` lowers
    to a partition-id HLO that XLA rejects under the partial-manual TP
    form: "PartitionId instruction is not supported for SPMD
    partitioning"); pipe_comm: the train-only aux channels, local
    [1, 1, T, ...] slices — ``"e"`` the hop-EF residual xs (its cotangent
    carries the NEW residuals out), ``"g"`` the gslot zeros (its cotangent
    carries the per-tick reduced stage gradient out).  Empty dict on the
    eval/no-hook paths."""
    stage = stage_arr[0]
    attn_fn = _pick_attn(cfg)
    M, T = num_micro, num_micro + pp - 1
    b = ids.shape[0] // M
    S = ids.shape[1]
    mb_ids = ids.reshape(M, b, S)
    mb_labels = labels.reshape(M, b, S)
    positions = jnp.broadcast_to(jnp.arange(S), (b, S))

    def embed(tok_ids):
        x = params["embed"]["tok"][tok_ids]
        if cfg.position == "learned":
            x = x + params["embed"]["pos"][:S][None]
        return x

    def head_loss(x, tok_labels):
        from ...models.transformer import logits_fn

        h = _norm(x, params["final_norm"]["scale"], params["final_norm"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        # logits_fn handles tied heads, phi-style head bias, and the
        # dict-valued weight-quantized head uniformly
        logits = logits_fn(cfg, params, h)[:, :-1]
        targets = tok_labels[:, 1:]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        # take_along_axis, NOT nll_pick: the one-hot contraction's
        # transpose ABORTS XLA's CPU backend inside this partial-manual
        # (pipe shard_map) region — same crash class as bf16 all-reduce
        # promotion there.  The gather's scatter-add backward is safe
        # here, and sequence sharding (nll_pick's reason to exist) does
        # not compose into the pipe loss stage.
        # clamp + mask (bert.py convention): take_along_axis would CLAMP
        # a -100 ignore-index to vocab 0 and backprop garbage there
        safe = jnp.maximum(targets, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        sel = (targets >= 0).astype(jnp.float32)
        # fold the 1/count into the (label-derived, rank-2) weight before
        # it meets nll: a scalar known-side divisor becomes a RANK-0
        # residual of the grad partial-eval, and the check_vma=False
        # shard_map transpose stacks residuals over a leading device dim
        # — which is unrepresentable for rank-0 and fails the spec check
        # (this very scalar broke every pipe backward before PR 16)
        w = sel / jnp.maximum(jnp.sum(sel), 1.0)
        return jnp.sum(nll * w)

    # tuple-of-tuples: the compressed ppermute verbs take perm as a
    # hashable nondiff argument (plain lax.ppermute accepts it too)
    perm = tuple((i, (i + 1) % pp) for i in range(pp))
    hop_spec = getattr(cfg, "pipe_hop_spec", None)
    e_all = pipe_comm.get("e") if isinstance(pipe_comm, dict) else None
    g_all = pipe_comm.get("g") if isinstance(pipe_comm, dict) else None
    plan = getattr(cfg, "pipe_overlap_plan", None)
    use_hook = plan is not None and g_all is not None

    from ...comm.collectives import compressed as _cc

    def hop(x, e_t):
        if hop_spec is None:
            return jax.lax.ppermute(x, PIPE_AXIS, perm)
        if e_t is not None:
            # error feedback: e_t compensates THIS tick's backward-wave
            # rotation; its cotangent is the tick's NEW residual (stacked
            # by the scan back into the [T, b, S, H] state layout)
            return _cc.ppermute_ef(x, e_t, perm, PIPE_AXIS, hop_spec)
        return _cc.ppermute(x, perm, PIPE_AXIS, hop_spec)

    stage_layers = params["layers"]
    if use_hook:
        # the reduced layer gradient leaves through the gslot cotangent
        # channel; stop_gradient makes the leaves' boundary cotangent a
        # SYMBOLIC zero, so the shard_map transpose emits no monolithic
        # fp psum for them (runtime/zero/overlap.py, module docstring)
        stage_layers = jax.lax.stop_gradient(stage_layers)

        def stage_fn(layers, xx):
            return _stage_apply(cfg, layers, xx, positions, attn_fn)

        @jax.custom_vjp
        def hooked_apply(layers, xx, g_t):
            return _stage_apply(cfg, layers, xx, positions, attn_fn)

        def hooked_fwd(layers, xx, g_t):
            out, vjp_fn = jax.vjp(stage_fn, layers, xx)
            return out, (vjp_fn,)

        def hooked_bwd(res, ct):
            (vjp_fn,) = res
            dlayers, dx = vjp_fn(ct)
            # this tick's per-stage layer-bucket reduce over the data
            # axis — issued INSIDE the backward scan trip, where drain
            # ticks are bubble time; the flat reduced payload rides out
            # as g_t's cotangent
            reduced = plan.reduce_stage_grads(dlayers)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, dlayers)
            return (zeros, dx, reduced)

        hooked_apply.defvjp(hooked_fwd, hooked_bwd)

    def step(carry, xs_t):
        t = xs_t["t"]
        buf, loss_acc, aux_acc = carry
        # stage 0 injects micro-batch t (clamped once t >= M); lax.cond keeps
        # the embedding gather off every other stage (only the taken branch
        # executes — the reference's LoadMicroBatch runs on stage 0 alone)
        x = jax.lax.cond(
            stage == 0,
            lambda: embed(mb_ids[jnp.minimum(t, M - 1)]).astype(buf.dtype),
            lambda: buf)
        if use_hook:
            x, aux = hooked_apply(stage_layers, x, xs_t["g"])
        else:
            x, aux = _stage_apply(cfg, stage_layers, x, positions, attn_fn)
        # last stage consumes output of micro-batch t - (pp - 1); the head
        # matmul + softmax run only there and only in the valid window
        mb_out = t - (pp - 1)
        valid = jnp.logical_and(stage == pp - 1, mb_out >= 0)
        # the accumulators (and every known-side scalar that feeds them)
        # are kept RANK-1 [1]: grad partial-eval saves known values the
        # backward needs as residuals, and the check_vma=False shard_map
        # transpose stacks residuals over a leading device dim — rank-0
        # residuals are unrepresentable there and fail the spec check
        # (this broke every pipe backward before PR 16; e.g. the aux
        # accumulator stays on the known side for non-MoE models)
        loss_t = jax.lax.cond(
            valid,
            lambda: head_loss(x, mb_labels[jnp.maximum(mb_out, 0)]).reshape(1),
            lambda: jnp.zeros((1,), jnp.float32))
        loss_acc = loss_acc + loss_t
        # every stage contributes ITS layers' aux (MoE router balance), but
        # only for ticks where it holds a real micro-batch (stage s at tick t
        # processes micro t - s); warm-up/drain garbage is excluded
        aux_valid = jnp.logical_and(t >= stage, t - stage < M)
        aux_acc = aux_acc + jnp.where(aux_valid, aux.reshape(1), 0.0)
        buf = hop(x, xs_t.get("e"))
        return (buf, loss_acc, aux_acc), None

    H = cfg.hidden_size
    xs = {"t": jnp.arange(T)}
    if e_all is not None:
        xs["e"] = e_all[0, 0]  # local [T, b, S, H] fp32 residual slices
    if use_hook:
        xs["g"] = g_all[0, 0]  # local [T, F] gslot zeros
    buf0 = jnp.zeros((b, S, H), params["embed"]["tok"].dtype)
    (buf, loss, aux), _ = jax.lax.scan(
        step, (buf0, jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.float32)),
        xs)
    # only the last stage holds the loss; share it across the pipe ring
    loss = jax.lax.psum(loss, PIPE_AXIS) / M
    aux = jax.lax.psum(aux, PIPE_AXIS) / M
    # average over data-parallel batch shards
    for ax in BATCH_AXES:
        loss = jax.lax.pmean(loss, ax)
        aux = jax.lax.pmean(aux, ax)
    return (loss + aux)[0]


def pipelined_causal_lm(cfg: TransformerConfig, num_microbatches: int = 4,
                        name: str = "pipelined-lm") -> ModelSpec:
    """Build a ModelSpec whose loss_fn runs the full pipeline schedule.

    The engine uses it like any model; ``gradient_accumulation`` inside the
    pipeline = ``num_microbatches`` (set engine gas=1).  On a pipe=1
    mesh the loss is the plain ``causal_lm_loss``: no schedule to run.
    """
    if cfg.post_norm:
        raise NotImplementedError("pipelined_causal_lm: post_norm "
                                  "(encoder-style) models are unsupported")
    rules = pipeline_partition_rules(cfg)

    def loss_fn(params, batch, rng):
        topo = get_topology()
        pp = topo.pipe_parallel_size
        if isinstance(batch, dict):
            ids = batch["input_ids"]
            labels = batch.get("labels", ids)
        else:
            ids, labels = batch, batch
        # train-only aux channels (engine-injected): popped BEFORE the
        # param specs are derived so the sharding plan never sees them
        pipe_comm = {}
        if isinstance(params, dict) and "_pipe_comm" in params:
            params = dict(params)
            pipe_comm = params.pop("_pipe_comm")
        if pp == 1:
            from ...models.transformer import causal_lm_loss

            return causal_lm_loss(cfg, params, batch, rng)

        from ...runtime.zero.strategy import ZeroShardingPlan

        plan = ZeroShardingPlan(topo, None, rules)
        param_specs = plan.tree_specs(params, "param")
        # With TP (or SP) inside the stages, the shard_map goes PARTIAL-
        # manual: only the pipe + batch axes are manual (the body
        # ppermutes over pipe and pmeans over batch); the model/sequence
        # axes stay AUTO — GSPMD keeps partitioning the attention/MLP
        # matmuls from the params' own shardings and inserts the TP
        # collectives inside each stage.  Under a fully manual map a
        # model-sharded wqkv would arrive as a local half and the
        # global-head reshape in the shared layer code would be wrong.
        # Pure pipe x data stays FULLY manual: the partial-manual form
        # trips an XLA CPU-backend crash for bf16 (AllReducePromotion,
        # "invalid binary instruction opcode copy") even with the auto
        # axes at size 1, and the fully manual form is field-proven there.
        from ...parallel.mesh import MODEL_AXIS, SEQ_AXIS

        tp_in_play = (topo.axis_size(MODEL_AXIS) > 1
                      or topo.axis_size(SEQ_AXIS) > 1)
        manual = ((PIPE_AXIS,) + BATCH_AXES if tp_in_play
                  else tuple(topo.mesh.axis_names))

        def _manual_only(spec):
            ent = []
            for e in spec:
                axes = (e if isinstance(e, tuple) else (e,)) if e else ()
                kept = tuple(a for a in axes if a in manual)
                ent.append(kept if len(kept) > 1 else
                           (kept[0] if kept else None))
            return P(*ent)

        manual_specs = jax.tree_util.tree_map(
            _manual_only, param_specs,
            is_leaf=lambda x: isinstance(x, P))
        body = functools.partial(_pipe_body, cfg=cfg, num_micro=num_microbatches,
                                 pp=pp)
        # aux channels are [pp, Dw, T, ...] globals split over pipe x data
        # (partitioned inputs — the boundary transpose is a plain
        # concatenate, no collective)
        from ...parallel.mesh import DATA_AXIS

        comm_specs = jax.tree_util.tree_map(
            lambda _: P(PIPE_AXIS, DATA_AXIS), pipe_comm)
        fn = shard_map(
            body, mesh=topo.mesh,
            in_specs=(manual_specs, P(BATCH_AXES, None), P(BATCH_AXES, None),
                      P(PIPE_AXIS), comm_specs),
            out_specs=P(), axis_names=set(manual), check_vma=False)
        stage_arr = jnp.arange(pp, dtype=jnp.int32)
        return fn(params, ids, labels, stage_arr, pipe_comm)

    spec = ModelSpec(
        init_params=lambda rng: init_transformer_params(cfg, rng),
        loss_fn=loss_fn,
        partition_rules=rules,
    )
    spec.config = cfg
    spec.num_microbatches = num_microbatches
    return spec
