"""Activation checkpointing.

Reference: ``runtime/activation_checkpointing/checkpointing.py`` —
``CheckpointFunction`` (:488) with partitioned activations across MP ranks
(:377), CPU checkpointing, RNG state tracking.

TPU: rematerialization is ``jax.checkpoint`` with a policy; "partitioned
activations" is a sharding constraint on the saved residuals; RNG is
functional (keys thread through), so no state tracker is needed.  The
module keeps the reference's configure()/checkpoint() module-level API so
ported code works.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax

from ...utils.logging import logger

_CONFIG = {
    "partition_activations": False,
    "cpu_checkpointing": False,
    "policy": "nothing_saveable",
    "number_checkpoints": None,
    "profile": False,
}

#: ``checkpoint_name`` tags on what the Pallas kernels' ``custom_vjp`` forward
#: rules return (``ops/pallas/``: flash attention's output and its row
#: log-sum-exp, the grouped expert matmul's product, the rows the expert
#: dispatch laid out).  Only a differentiated call traces a forward rule, so
#: no served program carries them.  A recomputed block that keeps them finds
#: the kernels' outputs made when its backward pass replays the block.
FLASH_OUT, FLASH_LSE, GROUPED_MATMUL_OUT, MOE_DISPATCH_ROWS = \
    KERNEL_RESIDUALS = ("flash_out", "flash_lse", "grouped_matmul_out",
                        "moe_dispatch_rows")

#: what ``remat: true`` keeps unless the model's ``remat_policy`` says
#: otherwise
DEFAULT_POLICY = "products_saveable"


def _products_saveable():
    """Keep what costs matrix-unit time, replay what is elementwise: the
    outputs of XLA's own products without batch dimensions and of the
    kernels (``KERNEL_RESIDUALS``) are saved; norms, rotary, the router's
    softmax and top-k, activation products, convolution taps and relayouts
    are recomputed from the block's input."""
    cp = jax.checkpoint_policies
    return cp.save_from_both_policies(
        cp.dots_with_no_batch_dims_saveable,
        cp.save_only_these_names(*KERNEL_RESIDUALS))


def _offload_dots():
    return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
        "device", "pinned_host")


POLICY_MAP = {
    # DeepSpeed-ish names -> jax.checkpoint_policies (a name of that module,
    # or a function that builds the policy)
    DEFAULT_POLICY: _products_saveable,
    "nothing_saveable": "nothing_saveable",
    "everything_saveable": "everything_saveable",
    "dots_saveable": "dots_saveable",
    "checkpoint_dots": "dots_saveable",
    "dots_with_no_batch_dims_saveable": "dots_with_no_batch_dims_saveable",
    "save_anything_except_these_names": None,
    "offload_dots": _offload_dots,
}


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None,
              policy: Optional[str] = None) -> None:
    """Reference-compatible configure (checkpointing.py:892)."""
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing", None)
        if ac is not None:
            _CONFIG["partition_activations"] = ac.partition_activations
            _CONFIG["cpu_checkpointing"] = ac.cpu_checkpointing
            _CONFIG["policy"] = ac.policy
            _CONFIG["number_checkpoints"] = ac.number_checkpoints
            _CONFIG["profile"] = ac.profile
    if partition_activations is not None:
        _CONFIG["partition_activations"] = partition_activations
    if checkpoint_in_cpu is not None:
        _CONFIG["cpu_checkpointing"] = checkpoint_in_cpu
    if num_checkpoints is not None:
        _CONFIG["number_checkpoints"] = num_checkpoints
    if policy is not None:
        _CONFIG["policy"] = policy


def get_policy(name: Optional[str] = None):
    name = name or _CONFIG["policy"]
    mapped = POLICY_MAP.get(name, name)
    if mapped is None:
        return None
    pol = mapped() if callable(mapped) \
        else getattr(jax.checkpoint_policies, mapped, None)
    if pol is None:
        logger.warning(f"unknown remat policy '{name}'; saving nothing")
    if _CONFIG["cpu_checkpointing"]:
        # offload saved residuals to host memory (ZeRO-R cpu checkpointing)
        try:
            return jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded="all",
                offload_src="device", offload_dst="pinned_host")
        except Exception:
            return pol
    return pol


def checkpoint(function: Callable, *args) -> Any:
    """Reference-compatible functional API: runs ``function`` under remat
    (CheckpointFunction.apply equivalent)."""
    wrapped = jax.checkpoint(function, policy=get_policy())
    return wrapped(*args)


def checkpoint_wrapper(function: Callable, policy: Optional[str] = None) -> Callable:
    return jax.checkpoint(function, policy=get_policy(policy))


def is_configured() -> bool:
    return True
