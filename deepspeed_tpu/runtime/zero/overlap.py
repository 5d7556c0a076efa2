"""Fine-grained compute/collective overlap for the fused train step.

Today's step compiles the ZeRO gradient exchange as one post-backward
block: the backward scan accumulates every layer's cotangent into the
stacked gradient buffer and GSPMD places the data-axis reduce wherever
its propagation lands it — in practice hoisted out of the layer loops,
serialized against nothing.  That is the exposed-communication problem
T3 (PAPERS.md) attacks with fine-grained tracking/triggering, and Domino
solves for TP by making the overlap *be* the dataflow graph.

This module is the ZeRO-side analogue.  Sharding *constraints* cannot
pin a reduction point (GSPMD folds them into propagation — measured:
at stage 1 a replicated cotangent constraint makes the partitioner
replicate the whole backward, 6x FLOPs), so the scanned transformer
block is instead wrapped in a **shard_map over the data axis** (other
mesh axes stay auto/GSPMD — TP rules untouched), where collectives are
explicit ops the partitioner must execute in place:

* **stage <= 2** — layer params enter the body replicated; shard_map's
  transpose inserts an explicit ``psum`` over ``data`` for each leaf's
  cotangent *inside the backward scan trip*, right where the partial
  grads materialize.  A ``custom_vjp`` hook groups the cotangents into
  size-targeted buckets (``overlap_bucket_mb``,
  ``comm/collectives/bucketer.py``) between ``optimization_barrier``
  pairs, so each bucket forms one reduce wavefront the latency-hiding
  scheduler can hide under the next layer's backward compute.
* **stage 3** — layer params enter the body as their ZeRO shards and
  the hook's fwd issues an explicit ``lax.all_gather`` per leaf at the
  body top (bucket-barriered): with the 2x-unrolled scan
  (``zero3_param_prefetch``) each trip holds two independent
  gather->compute chains, so layer i+1's gather overlaps layer i's
  compute — the double buffer.  The gather's AD transpose is an
  explicit ``psum_scatter``: the grad reduce-scatter rides the
  backward loop for free, per layer, no handles or waits.

Residual discipline: the hooked (gathered) param values are tagged
``overlap_params`` and the body is checkpointed with a policy that
refuses to save the whole hook chain (:func:`_overlap_remat_policy`) —
the backward re-derives them (a re-gather at stage 3) instead of
saving every layer's gathered params, which would defeat stage-3
partitioning (the carry-based double buffer tried earlier failed
exactly this way; see the scan comment in models/transformer.py).

The wrap is value-identity — per-shard compute is the same arithmetic
and the explicit collectives compute the same sums — so overlap-on
training is bit-exact with overlap-off (tests/unit/test_overlap.py's
``test_overlap_bit_exact_*``).  Every
bucket logs a trace-time collective event (``grad_bucket_reduce``)
into the span ring; the engine publishes the exposure split
(``telemetry/overlap.py``) as
``deepspeed_tpu_train_overlapped_fraction`` /
``_exposed_collective_seconds``.

Compressed overlap (docs/COMM.md "Compressed overlap"): with a
``CompressionSpec`` on the plan the in-loop exchange moves codes + block
scales instead of fp32 — stage <= 2 buckets ride the shared two-hop
compressed all-reduce (or the hierarchical three-hop when the data axis
is split), stage 3's explicit ``psum_scatter`` becomes the quantized
reduce-scatter — with ONE error-feedback residual per bucket carried as
a train-state leaf (``TrainState.comm_errors``), so residuals survive
donation, checkpoint and preemption-resume bit-identically.

Mechanically the compressed path cannot let the cotangent cross the
shard_map boundary (a replicated input's transpose is a full-width fp
``psum`` — exactly the bytes being eliminated), so the hook threads two
aux channels per bucket through the scan as extra xs:

* ``gslot`` — a zeros input whose COTANGENT carries the reduced bucket
  gradient out (axis-sharded ``[L, W, S]``: every rank writes the
  identical reduced value into its own row, so the boundary transpose
  is communication-free and the engine collapses rows locally);
* ``eslot`` — the residual input whose cotangent carries the NEW
  residual (same shape; each rank's row is its own compensation).

The param leaves whose exchange rides the gslot channel are
``stop_gradient``-ed inside the body, so their boundary cotangent is a
symbolic zero — no psum is ever emitted for them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm.collectives.bucketer import assign_buckets, bucketed_map
from ...comm.collectives.codec import CompressionSpec
from ...telemetry.spans import record_event
from ...utils.logging import logger

#: checkpoint_name tag on hook outputs (see module docstring)
OVERLAP_TAG = "overlap_params"


def _overlap_remat_policy():
    """Residual policy for the wrapped block: save the default residual
    set EXCEPT the hook's (gathered) parameter values — those are
    re-derived in the backward loop from the sharded inputs (a
    re-gather at stage 3), never stacked per layer.

    ``save_anything_except_these_names(TAG)`` alone is NOT enough: the
    name tag sits on the hook's final output, and partial eval simply
    saves the nearest saveable ANCESTOR — the (identical) gather /
    barrier output right above the tag.  The whole hook chain must be
    unsaveable, and inside the wrapped body the hook is the only
    producer of ``all_gather`` / ``optimization_barrier`` values, so
    the policy blocks those primitives by NAME (stable public strings;
    everything else keeps the default residual choice)."""
    #: primitives only the hook emits inside the wrapped body — their
    #: outputs are the (gathered) param values that must be re-derived,
    #: not saved per layer
    blocked = ("name", "all_gather", "optimization_barrier", "psum_scatter")

    def policy(prim, *_args, **params):
        pname = getattr(prim, "name", str(prim))
        if pname == "name":
            return params.get("name") != OVERLAP_TAG
        return pname not in blocked

    return policy


class OverlapPlan:
    """Static (trace-time) description of the shard_map'd block wrap.

    Built once per engine from the abstract stacked layer tree; passed
    to the model per trace (``TransformerConfig.overlap_plan``, the
    same engine-set-per-trace pattern as ``qwz``).  Hashable by
    identity — it is a ``custom_vjp`` nondiff argument."""

    TAG = OVERLAP_TAG

    def __init__(self, mesh, axis: str, treedef, paths: Sequence[str],
                 leaf_specs: Sequence[P], gather_dims: Sequence[Optional[int]],
                 buckets: Sequence[Sequence[int]],
                 bucket_bytes: Sequence[int],
                 bucket_step_bytes: Sequence[int],
                 compression: Optional[CompressionSpec] = None,
                 hier_inner: int = 0, n_layers: int = 1,
                 slice_shapes: Sequence[Tuple[int, ...]] = ()):
        self.mesh = mesh
        self.axis = axis
        self.treedef = treedef
        self.paths = tuple(paths)
        self.leaf_specs = tuple(leaf_specs)
        self.gather_dims = tuple(gather_dims)
        self.buckets = tuple(tuple(b) for b in buckets)
        self.bucket_bytes = tuple(int(b) for b in bucket_bytes)
        #: per-optimizer-step coverage of each bucket (slice bytes x
        #: n_layers) — what the trace-time events report, so the span
        #: accounting adds up against the structural totals
        self.bucket_step_bytes = tuple(int(b) for b in bucket_step_bytes)
        #: in-loop codec (None = the PR-12 exact fp exchange, bit-compat)
        self.compression = compression
        #: > 0: the stage<=2 in-loop reduce takes the hierarchical
        #: three-hop shape (intra-slice reduce-scatter, quantized
        #: inter-slice exchange, intra-slice gather)
        self.hier_inner = int(hier_inner)
        self.n_layers = int(n_layers)
        self.slice_shapes = tuple(tuple(s) for s in slice_shapes)
        # per-bucket comm-channel layout (compressed mode): the flat
        # (non-gathered) leaves coalesce — block-ALIGNED, so bucketed ==
        # unbucketed stays bit-exact — into one payload of _gslot_sizes[k]
        # elements reduced by ONE two-hop/hier chain; gathered leaves
        # follow per-leaf.  The bucket's eslot holds the flat payload's
        # residual at [0, gslot_size) and each gathered leaf's full-slice
        # residual after it — ONE residual leaf per bucket.
        self._flat_idx: List[List[int]] = []
        self._gath_idx: List[List[int]] = []
        self._offsets: List[dict] = []
        self._gslot_sizes: List[int] = []
        self._eslot_sizes: List[int] = []
        if compression is not None:
            blk = compression.block
            for idxs in self.buckets:
                fi = [i for i in idxs if self.gather_dims[i] is None]
                gi = [i for i in idxs if self.gather_dims[i] is not None]
                offs, off = {}, 0
                for i in fi:
                    offs[i] = off
                    n = int(np.prod(self.slice_shapes[i] or (1,)))
                    off += -(-n // blk) * blk
                sflat = off
                for i in gi:
                    offs[i] = off
                    off += int(np.prod(self.slice_shapes[i] or (1,)))
                self._flat_idx.append(fi)
                self._gath_idx.append(gi)
                self._offsets.append(offs)
                self._gslot_sizes.append(sflat)
                self._eslot_sizes.append(off if compression.error_feedback
                                         else 0)

    # ------------------------------------------------------- comm channel
    @property
    def error_feedback(self) -> bool:
        return (self.compression is not None
                and self.compression.error_feedback)

    def eslot_key(self, k: int) -> str:
        return f"b{k:03d}"  # zero-padded: checkpoint key order == bucket order

    def init_errors(self):
        """Fresh per-bucket EF residual leaves for ``TrainState.comm_errors``
        (eager; engine init / loud reset).  Global ``[L, W, S]`` fp32,
        axis-sharded on W: each rank stores only its own compensation."""
        W = int(self.mesh.shape[self.axis])
        sh = NamedSharding(self.mesh, P(None, self.axis))
        return {
            self.eslot_key(k): jax.device_put(
                jnp.zeros((self.n_layers, W, self._eslot_sizes[k]),
                          jnp.float32), sh)
            for k in range(len(self.buckets))}

    def grad_slots(self):
        """In-trace zero gslots (the reduced-gradient cotangent channel);
        rebuilt every step — only the RESIDUALS are state."""
        W = int(self.mesh.shape[self.axis])
        sh = NamedSharding(self.mesh, P(None, self.axis))
        return tuple(
            jax.lax.with_sharding_constraint(
                jnp.zeros((self.n_layers, W, self._gslot_sizes[k]),
                          jnp.float32), sh)
            for k in range(len(self.buckets)))

    def residual_bytes(self) -> int:
        """Total bytes of EF residual state held in train state (the
        ``deepspeed_tpu_comm_compression_residual_bytes`` gauge)."""
        W = int(self.mesh.shape[self.axis])
        return sum(self.n_layers * W * s * 4 for s in self._eslot_sizes)

    def residual_norms(self, comm_errors) -> Dict[str, Any]:
        """Per-bucket L2 norm of the carried EF residuals (in-trace fp32
        scalars, keyed like ``init_errors``).  residual_bytes says how
        much compensation state exists STRUCTURALLY; these say how big
        the compensation actually IS — a bucket norm growing without
        bound means error feedback is diverging, not catching up.  Rides
        the numerics stats tree; the engine publishes it as the
        ``deepspeed_tpu_comm_compression_residual_norm`` gauge."""
        slots = comm_errors.get("overlap", {}) if comm_errors else {}
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for k, v in slots.items()}

    def eslot_state(self, comm_errors):
        """The eslot tree for this step: the carried train-state
        residuals under error feedback, zero-width placeholders when the
        codec runs straight-through (the hook signature is uniform)."""
        if self.error_feedback:
            return comm_errors["overlap"]
        W = int(self.mesh.shape[self.axis])
        return {self.eslot_key(k): jnp.zeros((self.n_layers, W, 0),
                                             jnp.float32)
                for k in range(len(self.buckets))}

    def comm_tuples(self, comm) -> Tuple[Tuple[Any, ...], Tuple[Any, ...]]:
        """Split the model-side comm tree ``{"g": seq, "e": dict}`` into
        the hook's positional (gslots, eslots) tuples, bucket-ordered."""
        g = tuple(comm["g"])
        e = tuple(comm["e"][self.eslot_key(k)]
                  for k in range(len(self.buckets)))
        return g, e

    def merge_comm_grads(self, layer_grads: Any, gslot_cts: Sequence[Any]
                         ) -> Any:
        """Engine-side (in-trace, post-``jax.grad``): replace the
        stop-gradient-zeroed flat-leaf grads with the reduced values the
        gslot cotangents carried out.  Every rank's ``[L, W, S]`` row
        holds the identical reduced payload, so the collapse is a LOCAL
        squeeze (out_specs claims replication; no collective)."""
        from ...utils.jax_compat import shard_map

        leaves = list(self.treedef.flatten_up_to(layer_grads))
        ks = [k for k in range(len(self.buckets))
              if self._flat_idx[k] and self._gslot_sizes[k]]
        if not ks:
            return layer_grads
        collapse = shard_map(
            lambda *gs: tuple(g[:, 0] for g in gs), mesh=self.mesh,
            in_specs=tuple(P(None, self.axis) for _ in ks),
            out_specs=tuple(P() for _ in ks), check_vma=False,
            axis_names={self.axis})
        cols = collapse(*[gslot_cts[k] for k in ks])
        for k, col in zip(ks, cols):
            for i in self._flat_idx[k]:
                off = self._offsets[k][i]
                n_i = int(np.prod(self.slice_shapes[i] or (1,)))
                leaves[i] = col[:, off:off + n_i].reshape(
                    (self.n_layers,) + self.slice_shapes[i])
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # ------------------------------------------------------------- model API
    def wrap_block(self, raw_block, has_mask: bool):
        """Wrap ``raw_block(x, positions, mask, layer_tree) -> (y, aux)``
        in the data-axis shard_map (model side; the scan body calls the
        result with the same signature).  ``has_mask=False`` drops the
        mask slot (shard_map in_specs cannot carry a None leaf)."""
        from ...utils.jax_compat import shard_map

        plan = self

        def body(x, positions, *rest):
            mask = rest[0] if has_mask else None
            leaves = rest[1:] if has_mask else rest
            leaves = _overlap_hook(tuple(leaves), plan)
            leaves = tuple(checkpoint_name(v, OVERLAP_TAG) for v in leaves)
            layer = jax.tree_util.tree_unflatten(plan.treedef, leaves)
            return raw_block(x, positions, mask, layer)

        # residual discipline INSIDE the body: the policy must see the
        # hook call and its name tags, and shard_map residuals are
        # opaque from outside — so the checkpoint sits under the
        # shard_map
        body = jax.checkpoint(body, policy=_overlap_remat_policy())

        bsp = P(self.axis)  # batch-leading operands shard the lead dim
        mask_specs = (bsp,) if has_mask else ()
        sm = shard_map(
            body, mesh=self.mesh,
            in_specs=(bsp, bsp) + mask_specs + self.leaf_specs,
            out_specs=(bsp, P()),
            check_vma=False, axis_names={self.axis})

        sm_c = None
        if self.compression is not None:
            nl, nb = len(self.paths), len(self.buckets)

            def body_c(x, positions, *rest):
                mask = rest[0] if has_mask else None
                rest = rest[1:] if has_mask else rest
                leaves = tuple(rest[:nl])
                gslots = tuple(rest[nl:nl + nb])
                eslots = tuple(rest[nl + nb:])
                # flat-path leaves deliver their gradient via the gslot
                # cotangent channel; stop_gradient makes their boundary
                # cotangent a SYMBOLIC zero, so the shard_map transpose
                # emits no fp psum for them
                prepped = tuple(
                    lax.stop_gradient(v) if plan.gather_dims[i] is None
                    else v for i, v in enumerate(leaves))
                out_leaves = _overlap_hook_comm(prepped, gslots, eslots,
                                                plan)
                out_leaves = tuple(checkpoint_name(v, OVERLAP_TAG)
                                   for v in out_leaves)
                layer = jax.tree_util.tree_unflatten(plan.treedef,
                                                     out_leaves)
                return raw_block(x, positions, mask, layer)

            body_c = jax.checkpoint(body_c, policy=_overlap_remat_policy())
            comm_specs = tuple(P(self.axis) for _ in range(2 * nb))
            sm_c = shard_map(
                body_c, mesh=self.mesh,
                in_specs=(bsp, bsp) + mask_specs + self.leaf_specs
                + comm_specs,
                out_specs=(bsp, P()),
                check_vma=False, axis_names={self.axis})

        world = int(self.mesh.shape[self.axis])

        def wrapped(x, positions, mask, layer_tree, comm=None):
            if comm is not None and x.shape[0] % world != 0:
                raise ValueError(
                    f"compressed overlap: batch {x.shape[0]} does not "
                    f"divide the data axis ({world}) — training batches "
                    "divide by construction; the eval path must not pass "
                    "comm state")
            if x.shape[0] % world != 0:
                # e.g. an eval_batch whose batch does not divide the
                # data axis: the wrap cannot shard it — run the plain
                # GSPMD block (training batches divide by construction)
                from ...utils.logging import warning_once

                warning_once(
                    f"overlap wrap bypassed: batch {x.shape[0]} does not "
                    f"divide the data axis ({world})")
                return raw_block(x, positions, mask, layer_tree)
            leaves, treedef = jax.tree_util.tree_flatten(layer_tree)
            if treedef != self.treedef:
                raise ValueError(
                    "overlap plan was built for a different layer structure "
                    f"(plan {self.treedef} vs model {treedef}); rebuild the "
                    "engine after changing the model")
            args = (x, positions) + ((mask,) if has_mask else ()) + tuple(leaves)
            if comm is not None and sm_c is not None:
                gslots, eslots = self.comm_tuples(comm)
                return sm_c(*(args + gslots + eslots))
            return sm(*args)

        return wrapped

    # ------------------------------------------------------------ internals
    def _fwd(self, leaves: Tuple[Any, ...]) -> Tuple[Any, ...]:
        """Inside the body: stage-3 leaves are local ZeRO shards — issue
        their all-gathers per bucket at the body top, barrier-pinned, so
        the unrolled trip's two chains start independent."""
        if all(d is None for d in self.gather_dims):
            return leaves
        out = list(leaves)
        for k, idxs in enumerate(self.buckets):
            group = jax.lax.optimization_barrier(
                tuple(out[i] for i in idxs))
            gathered = []
            for i, v in zip(idxs, group):
                d = self.gather_dims[i]
                if d is not None:
                    v = lax.all_gather(v, self.axis, axis=d, tiled=True)
                gathered.append(v)
            group = jax.lax.optimization_barrier(tuple(gathered))
            for i, v in zip(idxs, group):
                out[i] = v
        return tuple(out)

    def _bwd(self, cts: Tuple[Any, ...]) -> Tuple[Any, ...]:
        """Inside the transposed body: per bucket, group the cotangents
        between barriers and issue the gather transposes (an explicit
        ``psum_scatter`` — the per-layer grad reduce-scatter) as one
        wavefront per backward trip.  Identity (stage <= 2) leaves pass
        through barrier-grouped; shard_map's boundary then psums them
        over the axis — also inside the trip."""
        out: List[Any] = list(cts)
        for k, idxs in enumerate(self.buckets):
            group = jax.lax.optimization_barrier(
                tuple(out[i] for i in idxs))
            reduced = []
            for i, v in zip(idxs, group):
                d = self.gather_dims[i]
                if d is not None:
                    # all_gather's transpose, written out so the bucket
                    # barriers pin it: this rank keeps ITS shard of the
                    # summed cotangent
                    v = lax.psum_scatter(v, self.axis,
                                         scatter_dimension=d, tiled=True)
                reduced.append(v)
            group = jax.lax.optimization_barrier(tuple(reduced))
            # trace-time collective event (the comm._log convention):
            # one point per bucket per traced program, carrying the
            # bytes the bucket reduces — the overlap accountant reads
            # these against the compute spans
            _record_bucket_reduce(self.bucket_step_bytes[k], k, len(idxs))
            for i, v in zip(idxs, group):
                out[i] = v
        return tuple(out)

    def _bwd_compressed(self, cts: Tuple[Any, ...],
                        eslots: Tuple[Any, ...]):
        """Compressed in-loop exchange (inside the transposed body, per
        backward scan trip): per bucket, the flat leaves coalesce into
        ONE block-aligned payload reduced by the shared compressed
        two-hop (or hierarchical three-hop) — codes + scales on the
        wire — and each gathered (stage-3) leaf's ``psum_scatter``
        becomes a quantized reduce-scatter.  Error feedback compensates
        from the bucket's eslot row and the NEW residual leaves through
        the eslot cotangent; the reduced flat payload leaves through the
        gslot cotangent (see module docstring).

        Returns ``(leaf_cts, gslot_cts, eslot_cts)``."""
        from ...comm.collectives import compressed as _cc

        spec = self.compression
        ef = spec.error_feedback
        # reduce_scatter branches on spec.error_feedback itself, so the
        # bucket spec is used as-is in both modes
        rs_spec = spec
        out: List[Any] = list(cts)
        gslot_cts: List[Any] = []
        eslot_cts: List[Any] = []
        for k, idxs in enumerate(self.buckets):
            group = jax.lax.optimization_barrier(
                tuple(out[i] for i in idxs))
            vals = dict(zip(idxs, group))
            e_all = eslots[k][0] if ef else None  # local [S_e] row
            reduced = {}
            e_parts_g = []
            for i in self._gath_idx[k]:
                v = vals[i]
                d = self.gather_dims[i]
                if ef:
                    off = self._offsets[k][i]
                    n_i = int(np.prod(self.slice_shapes[i] or (1,)))
                    err = e_all[off:off + n_i].reshape(v.shape)
                    red, ne = _cc.reduce_scatter(
                        v, op="sum", axis=self.axis, spec=rs_spec,
                        scatter_dim=d, error=err)
                    e_parts_g.append(ne.reshape(-1))
                else:
                    red = _cc.reduce_scatter(v, op="sum", axis=self.axis,
                                             spec=rs_spec, scatter_dim=d)
                reduced[i] = red.astype(v.dtype)
            fi = self._flat_idx[k]
            new_e_flat = None
            if fi:
                sflat = self._gslot_sizes[k]
                err = e_all[:sflat] if ef else None
                R, new_e_flat = _compressed_bucket_reduce(
                    [vals[i] for i in fi], err, spec, self.axis,
                    self.hier_inner)
                gslot_cts.append(R[None])
                for i in fi:
                    # dies at the body's stop_gradient (symbolic zero at
                    # the boundary); the real value rode the gslot
                    reduced[i] = jnp.zeros_like(vals[i])
            else:
                gslot_cts.append(jnp.zeros((1, 0), jnp.float32))
            if ef:
                parts = ([new_e_flat] if new_e_flat is not None else []) \
                    + e_parts_g
                flat_e = (jnp.concatenate(parts) if len(parts) > 1
                          else parts[0])
                eslot_cts.append(flat_e[None].astype(jnp.float32))
            else:
                eslot_cts.append(jnp.zeros_like(eslots[k]))
            new_group = jax.lax.optimization_barrier(
                tuple(reduced[i] for i in idxs))
            _record_bucket_reduce(self.bucket_step_bytes[k], k, len(idxs),
                                  compressed=True, format=spec.format)
            for i, v in zip(idxs, new_group):
                out[i] = v
        return tuple(out), tuple(gslot_cts), tuple(eslot_cts)


def _compressed_bucket_reduce(leaves: Sequence[Any], error: Optional[Any],
                              spec: CompressionSpec, axis: str,
                              hier_inner: int):
    """The compressed IN-LOOP bucket reducer: coalesce the bucket's flat
    leaves through ``bucketer.bucketed_map`` — the ONE coalesce pipeline
    every bucketed reducer shares (lint: ``grad-overlap``) — into one
    block-aligned fp32 payload, then run ONE compressed all-reduce chain
    over it: the shared two-hop (all_to_all + all_gather, codes on the
    wire both hops) or, with ``hier_inner``, the hierarchical three-hop.

    Returns ``(reduced_flat_payload, new_error_or_None)``."""
    from ...comm.collectives import compressed as _cc
    from ...comm.collectives.hierarchical import hier_all_reduce

    ef = spec.error_feedback and error is not None
    run_spec = spec if ef else dataclasses.replace(spec,
                                                   error_feedback=False)
    holder = {}

    def reduce_flat(flat, _k):
        if hier_inner:
            r = hier_all_reduce(flat, op="sum", axis=axis, inner=hier_inner,
                                spec=run_spec,
                                error=error if ef else None)
            red, holder["e"] = r if ef else (r, None)
        elif ef:
            # hop2_ef=False: the hop-2 owner reinjection is slot-layout
            # dependent; only the layout-stable hop-1 residual keeps
            # bucketed == unbucketed bit-exact (see compressed.all_reduce)
            red, holder["e"] = _cc.all_reduce(
                flat, op="sum", axis=axis, spec=run_spec, error=error,
                out_dtype=jnp.float32, hop2_ef=False)
        else:
            red = _cc.all_reduce(flat, op="sum", axis=axis, spec=run_spec,
                                 out_dtype=jnp.float32)
        holder["R"] = red
        return red

    bucketed_map(leaves, 1 << 62, reduce_flat, out_dtype=jnp.float32,
                 align=spec.block)
    return holder["R"], holder.get("e")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _overlap_hook_comm(leaves: Tuple[Any, ...], gslots: Tuple[Any, ...],
                       eslots: Tuple[Any, ...], plan: OverlapPlan):
    """The compressed-overlap hook: forward identical to the exact hook
    (stage-3 gathers stay fp — gradient compression only); the backward
    routes every layer-bucket through the codec and hijacks the
    gslot/eslot input cotangents as the gradient/residual out-channels
    (they are scan xs, so the per-trip values stack into the
    ``[L, W, S]`` train-state layout)."""
    return plan._fwd(leaves)


def _overlap_hook_comm_fwd(leaves, gslots, eslots, plan):
    return plan._fwd(leaves), (eslots,)


def _overlap_hook_comm_bwd(plan, res, cts):
    (eslots,) = res
    return plan._bwd_compressed(cts, eslots)


_overlap_hook_comm.defvjp(_overlap_hook_comm_fwd, _overlap_hook_comm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _overlap_hook(leaves: Tuple[Any, ...], plan: OverlapPlan):
    return plan._fwd(leaves)


def _overlap_hook_fwd(leaves, plan):
    return plan._fwd(leaves), None


def _overlap_hook_bwd(plan, _res, cts):
    return (plan._bwd(cts),)


_overlap_hook.defvjp(_overlap_hook_fwd, _overlap_hook_bwd)


def record_tail_reduce(nbytes: int) -> None:
    """Trace-time event for gradient bytes NOT covered by the hook (the
    non-layer leaves — embeddings, head, final norm — whose reduce stays
    post-backward).  One owner site for the span name."""
    record_event("grad_tail_reduce", cat="comm", bytes=int(nbytes),
                 overlapped=False)


def _record_bucket_reduce(nbytes: int, bucket: int, leaves: int,
                          compressed: bool = False,
                          format: Optional[str] = None) -> None:
    """ONE owner site for the ``grad_bucket_reduce`` trace event (the
    exact and compressed in-loop reducers share it; the span lint pins
    single ownership)."""
    attrs = dict(bytes=int(nbytes), bucket=int(bucket), leaves=int(leaves),
                 overlapped=True)
    if compressed:
        attrs.update(compressed=True, format=format)
    record_event("grad_bucket_reduce", cat="comm", **attrs)


def _entry_axes(entry) -> tuple:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def build_overlap_plan(zero_plan, abstract_layers: Any, *,
                       bucket_bytes: int, axis: str, stage: int,
                       grad_dtype,
                       compression: Optional[CompressionSpec] = None,
                       hier_inner: int = 0) -> Optional[OverlapPlan]:
    """Derive the wrap's static plan from the stacked layer tree.

    ``abstract_layers``: ``state.params["layers"]`` (stacked, leading
    dim = n_layers) — shapes/dtypes only.  ``axis``: the (single) batch
    mesh axis the wrap manages manually.  At ``stage`` 3 each leaf's
    in-body spec is its live ZeRO shard (gathered explicitly by the
    hook); below 3 the leaves enter replicated over ``axis``.
    ``compression``/``hier_inner``: the in-loop codec and hierarchy
    split for the compressed-overlap path (None/0 = exact fp exchange).
    """
    from .strategy import _path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_layers)
    if not flat:
        return None
    mesh = zero_plan.topology.mesh
    paths, leaf_specs, gather_dims, sizes, step_sizes = [], [], [], [], []
    slice_shapes = []
    grad_itemsize = np.dtype(grad_dtype).itemsize
    for path, leaf in flat:
        pstr = "layers/" + _path_str(path)
        shape = tuple(leaf.shape)
        paths.append(pstr)
        n_layers = shape[0] or 1
        slice_shapes.append(shape[1:])
        step_sizes.append(int(np.prod(shape)) * grad_itemsize)
        sizes.append(int(np.prod(shape)) // n_layers * grad_itemsize)
        gdim = None
        if stage >= 3:
            # the live param's stacked spec, restricted to `axis`, minus
            # the leading layer dim = where this leaf's ZeRO shard lives
            # inside the body (and therefore its explicit gather dim)
            full = zero_plan.param_spec(pstr, shape)
            for dim, entry in enumerate(tuple(full)[1:]):
                if axis in _entry_axes(entry):
                    gdim = dim
                    break
        if gdim is None:
            leaf_specs.append(P(*((None,) * (len(shape) - 1))))
        else:
            entries = [None] * (len(shape) - 1)
            entries[gdim] = axis
            leaf_specs.append(P(*entries))
        gather_dims.append(gdim)
    buckets = assign_buckets(sizes, bucket_bytes)
    bucket_sizes = [sum(sizes[i] for i in b) for b in buckets]
    bucket_step = [sum(step_sizes[i] for i in b) for b in buckets]
    logger.info(
        f"overlap plan: {len(flat)} layer leaves -> {len(buckets)} "
        f"bucket(s) (target {bucket_bytes / 2**20:.1f} MB, stage {stage}, "
        f"gathered={sum(d is not None for d in gather_dims)}"
        + (f", {compression.format} in-loop wire"
           + (" + EF" if compression.error_feedback else "")
           + (f", hier inner={hier_inner}" if hier_inner else "")
           if compression is not None else "") + ")")
    n_layers = tuple(flat[0][1].shape)[0] or 1
    return OverlapPlan(mesh, axis, treedef, paths, leaf_specs, gather_dims,
                       buckets, bucket_sizes, bucket_step,
                       compression=compression, hier_inner=hier_inner,
                       n_layers=n_layers, slice_shapes=slice_shapes)
