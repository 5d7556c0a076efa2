"""Model contract.

The reference wraps a ``torch.nn.Module`` whose forward returns a loss (or
outputs fed to a criterion).  The TPU engine needs three things, expressed
functionally so they compile:

  * ``init_params(rng) -> params``        (pytree of arrays)
  * ``loss_fn(params, batch, rng) -> scalar loss``  (train step body)
  * ``partition_rules() -> [(regex, PartitionSpec)]``  (TP/EP shardings; may
    be empty — ZeRO axes are added by the planner)

``ModelSpec`` adapts plain functions or flax.linen modules onto that
contract (the analogue of ``deepspeed.initialize(model=...)`` accepting any
nn.Module, __init__.py:78).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
from jax.sharding import PartitionSpec as P


class ModelSpec:
    def __init__(self,
                 init_params: Callable[[Any], Any],
                 loss_fn: Callable[[Any, Any, Any], Any],
                 partition_rules: Optional[Sequence[Tuple[str, P]]] = None,
                 apply_fn: Optional[Callable] = None,
                 flops_per_sample: Optional[float] = None,
                 buffers: Sequence[str] = ()):
        self.init_params = init_params
        #: regexes over parameter paths: leaves that are state the optimizer
        #: must leave as they are (no update, no weight decay) — they are
        #: cast, sharded and checkpointed with the parameters
        self.buffers = tuple(buffers)
        self.loss_fn = loss_fn
        self._partition_rules = list(partition_rules or [])
        self.apply_fn = apply_fn  # inference/eval forward (params, batch) -> outputs
        self.flops_per_sample = flops_per_sample

    def partition_rules(self) -> List[Tuple[str, P]]:
        return self._partition_rules

    # -- adapters ------------------------------------------------------------
    @staticmethod
    def from_flax(module: Any, example_batch: Any,
                  loss_fn: Optional[Callable[[Any, Any], Any]] = None,
                  partition_rules: Optional[Sequence[Tuple[str, P]]] = None,
                  batch_to_inputs: Optional[Callable[[Any], tuple]] = None) -> "ModelSpec":
        """Wrap a flax.linen module.

        ``batch_to_inputs(batch)`` -> positional args for ``module.apply``;
        default treats the batch as a (inputs, targets) pair and passes
        inputs.  ``loss_fn(outputs, batch)`` -> scalar; default assumes the
        module itself returns the loss.
        """
        if batch_to_inputs is None:
            def batch_to_inputs(batch):
                if isinstance(batch, (tuple, list)):
                    return (batch[0],)
                return (batch,)

        def init_params(rng):
            return module.init(rng, *batch_to_inputs(example_batch))

        def _loss(params, batch, rng):
            kwargs = {}
            if rng is not None:
                kwargs["rngs"] = {"dropout": rng}
            out = module.apply(params, *batch_to_inputs(batch), **kwargs)
            if loss_fn is not None:
                return loss_fn(out, batch)
            return out

        def apply_fn(params, batch):
            return module.apply(params, *batch_to_inputs(batch))

        rules = list(partition_rules or [])
        if not rules and hasattr(module, "partition_rules"):
            rules = list(module.partition_rules())
        return ModelSpec(init_params, _loss, rules, apply_fn)

    @staticmethod
    def from_functions(init_params: Callable, loss_fn: Callable,
                       partition_rules=None, apply_fn=None) -> "ModelSpec":
        return ModelSpec(init_params, loss_fn, partition_rules, apply_fn)


def as_model_spec(model: Any, example_batch: Any = None, loss_fn=None,
                  partition_rules=None) -> ModelSpec:
    if isinstance(model, ModelSpec):
        return model
    if hasattr(model, "init_params") and hasattr(model, "loss_fn"):
        return ModelSpec(model.init_params, model.loss_fn,
                         model.partition_rules() if hasattr(model, "partition_rules") else None,
                         getattr(model, "apply_fn", None),
                         getattr(model, "flops_per_sample", None))
    # flax linen module duck-typing
    if hasattr(model, "init") and hasattr(model, "apply"):
        if example_batch is None:
            raise ValueError("Wrapping a flax module requires example_batch for init")
        return ModelSpec.from_flax(model, example_batch, loss_fn, partition_rules)
    raise TypeError(f"Cannot adapt {type(model)} to ModelSpec")
