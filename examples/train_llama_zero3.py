"""Train a llama model with ZeRO-3 + tensor parallelism on a device mesh.

Runs anywhere:
  # 8-virtual-device CPU mesh
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/train_llama_zero3.py
  # real TPU slice: just run it (mesh axes spread over the chips)
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.llama import llama_model


def main():
    n_dev = len(jax.devices())
    model = llama_model("tiny" if n_dev <= 8 else "160m", max_seq_len=128)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3},
        "mesh": {"model": 2 if n_dev % 2 == 0 else 1, "data": -1},
        "gradient_clipping": 1.0,
        "steps_per_print": 10,
    })
    rng = np.random.RandomState(0)
    dp = engine.topology.dp_world_size
    vocab = model.config.vocab_size

    for step in range(50):
        ids = rng.randint(0, vocab, (2, 2 * dp, 128)).astype(np.int32)
        loss = engine.train_batch({"input_ids": jnp.asarray(ids)})
        if step % 10 == 0:
            print(f"step {step:3d}  loss {float(loss):.4f}")

    engine.save_checkpoint("/tmp/llama_ckpt_example")
    print("checkpoint saved; done")


if __name__ == "__main__":
    main()
