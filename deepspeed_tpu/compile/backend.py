"""DeepCompile equivalent: compiler passes over the training step.

Reference parity: ``deepspeed/compile/`` — a torch.compile backend
(compile/backend.py) plus graph passes (compile/passes/): ``zero3_compile``
(turn ZeRO-3 hooks into graph ops), ``prefetch`` (schedule allgathers
early), ``selective_gather`` (keep hot params resident), and
``offload_adam_states`` / ``offload_activation`` (move state/activations to
host inside the compiled graph), with C++ runtime support in
csrc/compile/.  The engine API is ``engine.compile()`` (engine.py:4243).

On TPU the training step is *already* one compiled XLA program, so the
first three passes are the compiler's own job: XLA SPMD schedules the
ZeRO allgathers/reduce-scatters and its latency-hiding scheduler overlaps
them with compute — there is nothing to rewrite, and those passes reduce
to (logged) no-ops kept for config/API parity.  The passes that *do* have
a TPU-side transformation:

* ``offload_adam_states`` — re-place the optimizer-state pytree in host
  memory (``memory_kind='pinned_host'``) and re-jit the step so XLA
  streams moments in/out around the update (reference
  compile/passes/offload_adam_states.py).
* ``offload_activation``  — rebuild the model's remat policy to
  rematerialize (and where supported, host-offload) activations
  (reference compile/passes/offload_activation.py).

Every pass is ``(engine) -> None`` and is recorded on
``engine.compile_passes_applied``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional

import jax

from ..utils.logging import logger

PassFn = Callable[[Any], None]
PASS_REGISTRY: Dict[str, PassFn] = {}


def shape_signature(*trees: Any) -> tuple:
    """Hashable ``(shape, dtype)`` signature of the array leaves of
    ``trees`` — the arg-shape key the recompilation sentinel
    (telemetry/compile_sentinel.py) attributes compiles with: a jitted
    program retraces exactly when this signature (or a static arg)
    changes, so an unchanged signature that still compiled is the
    steady-state-recompile smell.  Host-side only: reads ``.shape`` /
    ``.dtype`` avals, never device values."""
    parts = []
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            parts.append((tuple(getattr(leaf, "shape", ())),
                          str(getattr(leaf, "dtype",
                                      type(leaf).__name__))))
    return tuple(parts)


def _register(name: str):
    def deco(fn: PassFn) -> PassFn:
        PASS_REGISTRY[name] = fn
        return fn

    return deco


@_register("zero3_compile")
def _zero3_compile(engine) -> None:
    """ZeRO-3 gather/release as graph ops: on XLA the sharded step IS the
    graph; param allgathers are inserted by SPMD partitioning already."""
    logger.info("compile pass zero3_compile: handled by XLA SPMD partitioner "
                "(sharded train step is already one graph)")


@_register("prefetch")
def _prefetch(engine) -> None:
    """Early allgather scheduling: XLA's latency-hiding scheduler moves
    collective-starts ahead of consuming compute on TPU."""
    logger.info("compile pass prefetch: handled by the XLA latency-hiding "
                "scheduler")


@_register("selective_gather")
def _selective_gather(engine) -> None:
    """Keeping hot params resident: covered by the persistence-threshold
    behavior of the sharding plan (small params replicate, see
    zero/strategy.py)."""
    logger.info("compile pass selective_gather: small parameters already "
                "replicate under the sharding plan's persistence threshold")



def _pin_tree_to_host(engine, tree, what: str):
    """device_put every array leaf of ``tree`` into pinned host memory
    (scalars stay committed on device — annotating their placement trips
    the SPMD partitioner).  Returns the re-placed tree, or None with a
    warning where the backend lacks host memory spaces."""
    from jax.sharding import NamedSharding, PartitionSpec

    scalar_sh = NamedSharding(engine.topology.mesh, PartitionSpec())

    def to_host(x):
        if not hasattr(x, "sharding") or getattr(x, "ndim", 0) < 1:
            return jax.device_put(x, scalar_sh) if hasattr(x, "sharding") else x
        try:
            return jax.device_put(x, x.sharding.with_memory_kind("pinned_host"))
        except Exception as e:
            raise NotImplementedError(
                f"host memory spaces unavailable on this backend: {e}") from e

    try:
        return jax.tree_util.tree_map(to_host, tree)
    except NotImplementedError as e:
        logger.warning(f"{what} unavailable: {e}")
        return None


@_register("offload_adam_states")
def _offload_adam_states(engine) -> None:
    """Pin optimizer moments in host memory; XLA streams them through the
    update (reference compile/passes/offload_adam_states.py)."""
    state = engine.state
    if not state.opt_state:
        logger.warning("offload_adam_states: no device optimizer state "
                       "(host offload already active?); skipping")
        return
    new_opt = _pin_tree_to_host(engine, state.opt_state, "offload_adam_states")
    if new_opt is None:
        return
    import dataclasses as _dc

    engine.state = _dc.replace(state, opt_state=new_opt)
    # re-jit; on TPU the step program writes updated moments straight back
    # to host memory (out_shardings), on host platforms the engine re-pins
    # them eagerly after each boundary (_repin_opt_state)
    engine._compile_steps(opt_state_memory_kind="pinned_host")
    logger.info("compile pass offload_adam_states: optimizer state pinned "
                "to host memory")


@_register("offload_params")
def _offload_params(engine) -> None:
    """Pin the fp32 master params in host memory (ZeRO-Infinity
    ``offload_param``, reference zero/partition_parameters NVMe/CPU param
    path): XLA streams each step's param reads from pinned host memory, so
    HBM holds only activations + transient gathers.  Config-gated via
    zero_optimization.offload_param.device (engine __init__), also
    available as an explicit compile pass."""
    state = engine.state
    new_params = _pin_tree_to_host(engine, state.params, "offload_params")
    if new_params is None:
        return
    import dataclasses as _dc

    engine.state = _dc.replace(state, params=new_params)
    engine._compile_steps(param_memory_kind="pinned_host")
    logger.info("compile pass offload_params: master params pinned to host "
                "memory")


@_register("offload_activation")
def _offload_activation(engine) -> None:
    """Rematerialize activations (host-offload where the model supports it)
    — reference compile/passes/offload_activation.py."""
    model = engine.model
    cfg = getattr(model, "config", None)
    if cfg is None or not hasattr(cfg, "remat"):
        logger.warning("offload_activation: model has no remat-capable "
                       "config; skipping")
        return
    # mutate in place: the model's loss_fn closure captured this config
    # object, so the rebuilt step traces with the new remat policy
    cfg.remat = True
    cfg.remat_policy = "nothing_saveable"
    engine._compile_steps()
    logger.info("compile pass offload_activation: remat enabled "
                "(nothing_saveable policy)")


DEFAULT_PASSES = ("zero3_compile", "prefetch", "selective_gather")


def compile_engine(engine, backend: str = "xla",
                   passes: Optional[Iterable[str]] = None) -> Any:
    """``engine.compile()`` (reference engine.py:4243, compile/backend.py).

    Applies the named passes in order; unknown names raise.  Returns the
    engine for chaining.
    """
    if backend not in ("xla", "inductor", "eager"):
        raise ValueError(f"unknown compile backend '{backend}'")
    names: List[str] = list(passes if passes is not None else DEFAULT_PASSES)
    applied = []
    from ..telemetry.compile_sentinel import expect_recompile

    for name in names:
        if name not in PASS_REGISTRY:
            raise KeyError(f"unknown compile pass '{name}'; "
                           f"known: {sorted(PASS_REGISTRY)}")
        PASS_REGISTRY[name](engine)
        # a pass that re-jits the step legitimately compiles on the next
        # call — tell the sentinel so it is not flagged as steady-state
        expect_recompile(f"compile_pass:{name}")
        applied.append(name)
    existing = list(getattr(engine, "compile_passes_applied", []))
    engine.compile_passes_applied = existing + applied
    engine.is_compiled = True
    return engine
