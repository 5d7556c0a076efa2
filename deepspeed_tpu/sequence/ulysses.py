"""Ulysses sequence parallelism.

Reference: ``DistributedAttention`` (deepspeed/sequence/layer.py:331) —
all-to-all scatters the sequence dim and gathers the head dim before
attention, then the inverse after, so each rank runs full-sequence attention
on a subset of heads.

TPU-native: the two all-to-alls are *sharding constraints*.  Activations
arrive sequence-sharded (P(batch, "sequence", heads, d)); constraining q/k/v
to P(batch, None, "sequence", d) makes XLA emit exactly the head-scatter /
seq-gather all-to-all over ICI, and the output constraint restores
seq-sharding.  Uneven heads (n_heads % sequence_parallel_size != 0) are
first-class: the head axis is zero-padded to the next multiple of the
sequence group (the reference's ``uneven_heads_all2all`` pads its scatter
the same way), attention runs on the padded head set — heads are
independent, so pad heads never touch real outputs — and the pad heads
are dropped after the gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import BATCH_AXES, SEQ_AXIS, get_topology


def _constrain(x, spec):
    topo = get_topology()
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(topo.mesh, spec))


def _pad_heads(x, sp: int):
    """Zero-pad the head axis ([B, S, NH, D]) to a multiple of ``sp`` so
    the head-scatter all-to-all divides evenly."""
    pad = -x.shape[2] % sp
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def ulysses_attention(q, k, v, causal: bool = True, mask=None, inner=None):
    """Drop-in ``attn_fn`` for models/transformer.py ([B, S, NH, D])."""
    topo = get_topology()
    sp = topo.seq_parallel_size
    nh = q.shape[2]
    if inner is None:
        from ..utils.platform import on_tpu

        if on_tpu():
            from ..models.transformer import flash_on_mesh

            # heads ride the sequence axis between the two all-to-alls
            inner = functools.partial(flash_on_mesh, head_axes=(SEQ_AXIS,))
        else:
            from ..models.transformer import xla_attention as inner
    if sp <= 1:
        return inner(q, k, v, causal, mask)
    if k.shape[2] != nh and (nh % sp or k.shape[2] % sp or v.shape[2] % sp):
        # GQA-aware inner (fewer KV heads, e.g. via alst.ulysses_sp_
        # attention) with uneven groups: zero-padding q and kv by
        # different amounts would remap the q-head->kv-group ratio and
        # silently corrupt attention — keep the replicated fallback.
        # (transformer._block repeats grouped KV before attn_fn, so the
        # in-repo path always arrives here with equal head counts.)
        return inner(q, k, v, causal, mask)

    # uneven heads: pad the head axes up to the sequence group (a no-op
    # for divisible GQA), scatter, drop the pad heads after the gather
    q, k, v = (_pad_heads(t, sp) for t in (q, k, v))

    seq_spec = P(BATCH_AXES, SEQ_AXIS, None, None)
    head_spec = P(BATCH_AXES, None, SEQ_AXIS, None)
    # all-to-all #1: seq-sharded -> head-sharded (full sequence per rank)
    q, k, v = (_constrain(t, head_spec) for t in (q, k, v))
    out = inner(q, k, v, causal, mask)
    # all-to-all #2: back to seq-sharded
    out = _constrain(out, seq_spec)
    return out[:, :, :nh] if out.shape[2] != nh else out
