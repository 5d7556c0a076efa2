"""Serve and fine-tune a published Hugging Face checkpoint.

The reference's flow (init_inference over a downloaded model dir, or
HF Trainer + ds_config for fine-tuning) on this runtime:

    python examples/import_hf_checkpoint.py /path/to/llama-checkpoint

Works with llama / mistral / qwen2 / mixtral / gpt2 directories containing
config.json plus model.safetensors[.index.json] or pytorch_model.bin.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu


def main(model_dir: str) -> None:
    # --- inference: one call from checkpoint dir to generate -------------
    engine = deepspeed_tpu.init_inference(
        model_dir, {"dtype": "bf16", "replace_with_kernel_inject": True})
    prompt = jnp.asarray(
        np.random.RandomState(0).randint(0, 100, (1, 8)), jnp.int32)
    out = engine.generate(prompt, max_new_tokens=16, temperature=0.8,
                          top_p=0.95)
    print("generated ids:", np.asarray(out)[0, -16:])

    # --- fine-tune the same weights through the training engine ----------
    from deepspeed_tpu.checkpoint.hf_import import load_hf_model
    from deepspeed_tpu.models.llama import llama_model

    cfg, params = load_hf_model(model_dir)  # host-resident numpy tree
    trainer, *_ = deepspeed_tpu.initialize(
        model=llama_model(config=cfg),
        config={
            "train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-5}},
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 2},
        })
    # place the imported weights into the engine's sharded state
    import dataclasses

    shardings = jax.tree_util.tree_map(lambda x: x.sharding,
                                       trainer.state.params)
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, trainer.state.params)
    host = jax.tree_util.tree_map(lambda a, dt: np.asarray(a).astype(dt),
                                  params, dtypes)
    trainer.state = dataclasses.replace(
        trainer.state, params=jax.device_put(host, shardings))

    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg.vocab_size, (1, 1, 64)), jnp.int32)
    for step in range(3):
        loss = trainer.train_batch({"input_ids": ids})
        print(f"fine-tune step {step}: loss {float(loss):.4f}")


if __name__ == "__main__":
    main(sys.argv[1])
