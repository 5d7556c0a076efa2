"""Layer-reduction distillation: init a shallow student from a trained
teacher, then fine-tune it with a soft-target KD loss.

Reference flow: ``init_compression`` with a ``layer_reduction`` config
re-initializes the student from configured teacher layers
(compression/compress.py ``student_initialization``); training then mixes
the CE objective with Hinton-style KD against the teacher's logits.

Run (CPU mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/distill_student.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.compression.compress import (distillation_loss,
                                                init_compression)
from deepspeed_tpu.models.llama import llama_model
from deepspeed_tpu.models.transformer import (logits_fn, transformer_forward)
from deepspeed_tpu.parallel import mesh as mesh_mod


def main():
    rng = np.random.RandomState(0)
    batch = {"input_ids": jnp.asarray(
        rng.randint(0, 256, (1, 16, 64)).astype(np.int32))}

    # 1. a "trained" teacher (here: a few steps on the toy corpus)
    teacher_model = llama_model("tiny", max_seq_len=64, n_layers=4)
    engine, *_ = deepspeed_tpu.initialize(
        model=teacher_model,
        config={"train_micro_batch_size_per_gpu": 16,
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
                "bf16": {"enabled": True}})
    for step in range(30):
        loss = engine.train_batch(batch)
    print(f"teacher loss after 30 steps: {float(loss):.4f}")
    teacher = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                     engine.state.params)

    # 2. student: half the depth, layers 0 and 3 copied from the teacher
    student_model = llama_model("tiny", max_seq_len=64, n_layers=2)
    student0 = student_model.init_params(jax.random.PRNGKey(1))
    kd_config = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_number_layer": 2, "teacher_layer": [0, 3]}}}
    distilled, _ = init_compression(student0, kd_config,
                                    teacher_params=teacher)

    # 3. fine-tune with CE + KD (teacher logits precomputed per batch)
    t_cfg, s_cfg = teacher_model.config, student_model.config
    t_hidden, _ = transformer_forward(t_cfg, teacher, batch["input_ids"][0])
    t_logits = logits_fn(t_cfg, teacher, t_hidden)

    def kd_loss_fn(params, b, rng_):
        ce = student_model.loss_fn(params, b, rng_)
        s_hidden, _ = transformer_forward(s_cfg, params, b["input_ids"])
        s_logits = logits_fn(s_cfg, params, s_hidden)
        return 0.5 * ce + 0.5 * distillation_loss(s_logits, t_logits,
                                                  temperature=2.0)

    mesh_mod.reset_topology()
    student_engine, *_ = deepspeed_tpu.initialize(
        model=deepspeed_tpu.ModelSpec(lambda rng_: distilled, kd_loss_fn),
        config={"train_micro_batch_size_per_gpu": 16,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}})
    for step in range(20):
        loss = student_engine.train_batch(batch)
    print(f"student KD loss after 20 steps: {float(loss):.4f}")
    b0 = jax.tree_util.tree_map(lambda x: x[0], batch)
    print(f"student CE: {float(student_model.loss_fn(student_engine.state.params, b0, None)):.4f} "
          f"(random-init student would start near ln(256) = 5.55)")


if __name__ == "__main__":
    main()
