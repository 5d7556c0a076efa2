"""``shard_map`` under the positional ``(f, mesh, in_specs, out_specs)``
call shape the package uses, over ``jax.shard_map`` (the one installed JAX:
see pyproject.toml's pin)."""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` = the axes handled manually."""
    kw = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
