"""Sharded linear building blocks.

Reference parity: ``LinearLayer`` / ``LinearAllreduce``
(module_inject/layers.py) — the two primitives AutoTP swaps in for
``nn.Linear``.  Two TPU forms:

* SPMD form (``column_parallel`` / ``row_parallel``): the plain einsum plus
  a ``with_sharding_constraint``; inside ``jit`` under a mesh, XLA inserts
  the reduce the reference does with an explicit ``all_reduce``.
* Explicit form (``*_explicit``): for use inside ``shard_map`` where
  collectives are written by hand (``jax.lax.psum``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import MODEL_AXIS


def column_parallel(x: jnp.ndarray, w: jnp.ndarray,
                    b: Optional[jnp.ndarray] = None,
                    mesh=None, axis: str = MODEL_AXIS) -> jnp.ndarray:
    """y = x @ w with the output feature dim sharded over ``axis``.

    Reference ``LinearLayer`` (module_inject/layers.py): weight is
    column-sharded, output stays sharded for the next (row-parallel) matmul.
    """
    y = jnp.einsum("...i,io->...o", x, w)
    if b is not None:
        y = y + b
    if mesh is not None:
        spec = P(*((None,) * (y.ndim - 1) + (axis,)))
        y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, spec))
    return y


def row_parallel(x: jnp.ndarray, w: jnp.ndarray,
                 b: Optional[jnp.ndarray] = None,
                 mesh=None, axis: str = MODEL_AXIS) -> jnp.ndarray:
    """y = sum_over_axis(x_shard @ w_shard) + b.

    Reference ``LinearAllreduce``: weight is row-sharded; the partial
    products are summed over the model axis (XLA derives the all-reduce
    from the replicated output constraint).
    """
    y = jnp.einsum("...i,io->...o", x, w)
    if mesh is not None:
        spec = P(*((None,) * y.ndim))
        y = jax.lax.with_sharding_constraint(y, NamedSharding(mesh, spec))
    if b is not None:
        y = y + b
    return y


def column_parallel_explicit(x: jnp.ndarray, w_shard: jnp.ndarray,
                             b_shard: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Per-shard column matmul for shard_map bodies: no collective needed —
    each rank computes its slice of the output features."""
    y = jnp.einsum("...i,io->...o", x, w_shard)
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel_explicit(x_shard: jnp.ndarray, w_shard: jnp.ndarray,
                          b: Optional[jnp.ndarray] = None,
                          axis: str = MODEL_AXIS) -> jnp.ndarray:
    """Per-shard row matmul + psum for shard_map bodies (the explicit
    all-reduce of the reference's LinearAllreduce.forward)."""
    y = jax.lax.psum(jnp.einsum("...i,io->...o", x_shard, w_shard), axis)
    if b is not None:
        y = y + b
    return y
