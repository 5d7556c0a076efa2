"""LoRA fine-tuning: train low-rank adapters over a frozen (optionally
int8-quantized) base through the engine.

The adapters are the only trainable leaves — the ModelSpec's loss closes
over the frozen base, so ZeRO shards and the optimizer update touch the
adapter tree alone (reference OptimizedLinear + LoRAConfig,
deepspeed/linear/).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/finetune_lora.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.linear.optimized_linear import (LoRAConfig,
                                                   init_lora_linear,
                                                   lora_linear)


def main():
    rng = jax.random.PRNGKey(0)
    lora = LoRAConfig(lora_r=8, lora_alpha=16)
    in_dim, hidden, out_dim = 32, 64, 8

    # a tiny 2-layer "pretrained" MLP whose linears get LoRA adapters
    k1, k2 = jax.random.split(rng)
    layer1 = init_lora_linear(k1, in_dim, hidden, lora)
    layer2 = init_lora_linear(k2, hidden, out_dim, lora)
    frozen = {"l1": {k: v for k, v in layer1.items() if "lora" not in k},
              "l2": {k: v for k, v in layer2.items() if "lora" not in k}}
    adapters = {"l1": {k: v for k, v in layer1.items() if "lora" in k},
                "l2": {k: v for k, v in layer2.items() if "lora" in k}}

    def loss_fn(trainable, batch, _rng=None):
        x, y = batch
        p1 = {**frozen["l1"], **trainable["l1"]}
        p2 = {**frozen["l2"], **trainable["l2"]}
        h = jax.nn.gelu(lora_linear(p1, x, lora))
        pred = lora_linear(p2, h, lora)
        return jnp.mean((pred - y) ** 2)

    spec = deepspeed_tpu.ModelSpec(init_params=lambda rng: adapters,
                                   loss_fn=loss_fn)
    engine, *_ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 1},
    })

    dp = engine.topology.dp_world_size
    data_rng = np.random.RandomState(0)
    target = data_rng.randn(in_dim, out_dim).astype(np.float32)
    x_np = data_rng.randn(1, 8 * dp, in_dim).astype(np.float32)
    x = jnp.asarray(x_np)
    y = jnp.asarray(x_np[0] @ target)[None]
    losses = []
    for step in range(80):
        loss = engine.train_batch((x, y))  # device scalar; no per-step sync
        losses.append(loss)
        if step % 20 == 0:
            print(f"step {step:2d}  adapter-only loss {float(loss):.4f}")
    first, last = float(losses[0]), float(losses[-1])
    assert last < first * 0.5, "LoRA adapters failed to fit the batch"

    n_train = sum(x.size for x in jax.tree_util.tree_leaves(engine.state.params))
    n_total = n_train + sum(x.size for x in jax.tree_util.tree_leaves(frozen))
    print(f"trainable params: {n_train} / {n_total} "
          f"({100 * n_train / n_total:.1f}%) — done")


if __name__ == "__main__":
    main()
