"""Long-context training with ring-attention context parallelism.

The sequence dim is sharded over the "sequence" mesh axis; K/V blocks
rotate the ring via ppermute while each rank's queries stay resident —
per-rank activation memory is 1/sp of the full sequence.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/long_context_ring.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.llama import llama_model


def main():
    seq = 512  # global sequence; each of 8 ranks holds 64 tokens
    model = llama_model("tiny", max_seq_len=seq, attn_impl="ring",
                        loss_chunk=73)  # tiled logits-loss: 511 = 7*73
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "mesh": {"sequence": 8, "data": -1},
    })
    rng = np.random.RandomState(0)
    for step in range(20):
        ids = rng.randint(0, model.config.vocab_size, (1, 1, seq)).astype(np.int32)
        loss = engine.train_batch({"input_ids": jnp.asarray(ids)})
        if step % 5 == 0:
            print(f"step {step:2d}  loss {float(loss):.4f}")
    print("done")


if __name__ == "__main__":
    main()
