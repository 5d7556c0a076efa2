"""Device-mesh topology.

TPU-native replacement for the reference's process-group machinery
(``deepspeed/utils/groups.py``, ``runtime/pipe/topology.py``): instead of
creating torch.distributed process groups for DP/TP/PP/SP/EP, we build ONE
``jax.sharding.Mesh`` with named axes and express every parallel strategy as
a sharding over those axes.  XLA then inserts the collectives (over ICI
within a slice, DCN across slices).

Axes (sizes from ``MeshConfig``):
  pipe      pipeline stages          (reference: PipelineParallelGrid)
  data      data parallelism / ZeRO  (reference: data_parallel_group)
  expert    MoE expert parallelism   (reference: expert_parallel_group)
  sequence  Ulysses/ring seq-par     (reference: sequence_parallel_group)
  model     tensor parallelism       (reference: model_parallel_group)

The ZeRO sharding axes are ``("data", "expert", "sequence")`` for non-expert
parameters (those axes all see the same replica of a dense param, mirroring
``seq_data_parallel_group`` in the reference, engine.py:1835) and
``("data",)`` for expert parameters.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.config import MeshConfig
from ..utils.logging import logger

PIPE_AXIS = "pipe"
REPL_AXIS = "repl"  # MiCS replica groups: ZeRO shards within, replicates across
DATA_AXIS = "data"
EXPERT_AXIS = "expert"
SEQ_AXIS = "sequence"
MODEL_AXIS = "model"

ALL_AXES = (PIPE_AXIS, REPL_AXIS, DATA_AXIS, EXPERT_AXIS, SEQ_AXIS, MODEL_AXIS)
#: axes over which ZeRO partitions dense (non-expert) state.  The MiCS
#: "repl" axis is deliberately absent: state is sharded within a data group
#: and replicated across repl groups (reference zero/mics.py:447) — gradient
#: averaging across repl happens through the batch sharding alone.
ZERO_AXES = (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS)
#: axes over which ZeRO partitions expert state
EXPERT_ZERO_AXES = (DATA_AXIS,)
#: the batch dimension of inputs is sharded over these
BATCH_AXES = (REPL_AXIS, DATA_AXIS, EXPERT_AXIS)


class MeshTopology:
    """Builds and owns the global device mesh."""

    def __init__(self, config: Optional[MeshConfig] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        self.config = config or MeshConfig()
        devices = list(devices if devices is not None else jax.devices())
        n = len(devices)

        sizes = {
            PIPE_AXIS: self.config.pipe,
            REPL_AXIS: getattr(self.config, "repl", 1),
            DATA_AXIS: self.config.data,
            EXPERT_AXIS: self.config.expert,
            SEQ_AXIS: self.config.sequence,
            MODEL_AXIS: self.config.model,
        }
        fixed = math.prod(v for v in sizes.values() if v != -1)
        free = [k for k, v in sizes.items() if v == -1]
        if len(free) > 1:
            raise ValueError(f"At most one mesh axis may be -1, got {free}")
        if free:
            if n % fixed != 0:
                raise ValueError(
                    f"{n} devices not divisible by fixed axis product {fixed}")
            sizes[free[0]] = n // fixed
        elif fixed != n:
            raise ValueError(f"Mesh axis product {fixed} != device count {n}")

        shape = tuple(sizes[a] for a in ALL_AXES)
        from jax.experimental import mesh_utils

        # no enumeration-order fallback: a mesh that ignores the physical
        # topology runs, slowly, and nothing says why
        device_array = mesh_utils.create_device_mesh(shape, devices=devices)
        self.mesh = Mesh(device_array, ALL_AXES)
        self.axis_sizes = sizes
        logger.info(f"MeshTopology: {sizes} over {n} devices")

    # -- world sizes (reference groups.get_*_world_size) --------------------
    @property
    def world_size(self) -> int:
        return self.mesh.size

    def axis_size(self, axis: str) -> int:
        return self.axis_sizes[axis]

    @property
    def dp_world_size(self) -> int:
        """Data-parallel degree for batch-size math: everything that consumes
        distinct micro-batches (repl × data × expert axes; sequence ranks
        share a batch, pipeline/model ranks share a batch)."""
        return (self.axis_sizes[REPL_AXIS] * self.axis_sizes[DATA_AXIS]
                * self.axis_sizes[EXPERT_AXIS])

    @property
    def zero_world_size(self) -> int:
        return math.prod(self.axis_sizes[a] for a in ZERO_AXES)

    @property
    def model_parallel_size(self) -> int:
        return self.axis_sizes[MODEL_AXIS]

    @property
    def seq_parallel_size(self) -> int:
        return self.axis_sizes[SEQ_AXIS]

    @property
    def expert_parallel_size(self) -> int:
        return self.axis_sizes[EXPERT_AXIS]

    @property
    def pipe_parallel_size(self) -> int:
        return self.axis_sizes[PIPE_AXIS]

    # -- sharding helpers ---------------------------------------------------
    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def batch_sharding(self, with_seq: bool = False) -> NamedSharding:
        """Input batches: batch dim over data(+expert), seq dim optionally
        over the sequence axis (Ulysses-style sharded dataloader)."""
        if with_seq:
            return self.sharding(BATCH_AXES, SEQ_AXIS)
        return self.sharding(BATCH_AXES)

    def __enter__(self):
        self._ctx = self.mesh
        return self._ctx.__enter__()

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)


# --- global topology registry (reference deepspeed/utils/groups.py) ---------
_TOPOLOGY: Optional[MeshTopology] = None


def initialize_topology(config: Optional[MeshConfig] = None,
                        devices: Optional[Sequence[jax.Device]] = None) -> MeshTopology:
    global _TOPOLOGY
    _TOPOLOGY = MeshTopology(config, devices)
    return _TOPOLOGY


def get_topology() -> MeshTopology:
    global _TOPOLOGY
    if _TOPOLOGY is None:
        _TOPOLOGY = MeshTopology()
    return _TOPOLOGY


def peek_topology() -> Optional[MeshTopology]:
    """The initialized topology, or None — never creates one (safe to call
    from library code at trace time without the side effect of building a
    default mesh over all devices)."""
    return _TOPOLOGY


def reset_topology() -> None:
    global _TOPOLOGY
    _TOPOLOGY = None


# reference-compatible getters (deepspeed/utils/groups.py)
def get_data_parallel_world_size() -> int:
    return get_topology().dp_world_size


def get_model_parallel_world_size() -> int:
    return get_topology().model_parallel_size


def get_expert_parallel_world_size() -> int:
    return get_topology().expert_parallel_size


def get_sequence_parallel_world_size() -> int:
    return get_topology().seq_parallel_size
