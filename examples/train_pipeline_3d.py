"""3D-parallel training: pipeline x tensor x data on one mesh.

The llama trunk runs as pipeline stages over the 'pipe' axis (1F1B over
ppermute, per-tick remat so activation memory doesn't scale with
micro-batch count), tensor-parallel within each stage over 'model', and
data-parallel over the rest — BASELINE config #1's PipelineEngine flow
composed the TPU way.  Only the pipe and batch axes are manual inside
the pipeline's shard_map; the model axis stays auto, so GSPMD inserts
the tensor-parallel collectives within each stage.  (For stage-count
resharding of generic LayerSpec pipelines — resuming pipe=2 params on a
pipe=4 cluster — see ``PipelineModule.reshard_params``.)

Run on the 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_pipeline_3d.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.llama import llama_config
from deepspeed_tpu.parallel.mesh import MeshConfig, initialize_topology
from deepspeed_tpu.runtime.pipe.engine import pipelined_causal_lm

SEQ = 64


def main():
    initialize_topology(MeshConfig(pipe=2, model=2, data=-1))
    cfg = llama_config("tiny", max_seq_len=SEQ)
    model = pipelined_causal_lm(cfg, num_microbatches=2)

    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model,
        config={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,  # micro-batching is the pipe's
            "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
            # fp32 here: bf16 TP all-reduces inside the pipe's manual
            # region trip an XLA CPU-backend AllReducePromotion crash on
            # the virtual mesh; the TPU backend reduces bf16 natively
            "zero_optimization": {"stage": 1},
            "mesh": {"pipe": 2, "model": 2, "data": -1},
        },
        topology=deepspeed_tpu.get_topology(),
    )

    rng = np.random.RandomState(0)
    corpus = rng.randint(0, cfg.vocab_size, (8, 4, SEQ)).astype(np.int32)
    for step in range(40):
        ids = corpus[step % len(corpus)]
        loss = engine.train_batch({"input_ids": jnp.asarray(ids)[None]})
        if step % 10 == 0:
            print(f"step {step:3d}  loss {float(loss):.4f}  "
                  f"lr {engine.get_lr()[0]:.2e}")
    print(f"final loss {float(loss):.4f}")
    assert np.isfinite(float(loss))


if __name__ == "__main__":
    main()
