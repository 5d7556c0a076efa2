"""Data-efficiency pretraining: analyzer -> curriculum -> variable batch.

The reference's data-efficiency library end to end (curriculum learning +
data analysis, runtime/data_pipeline):

  1. map-reduce the corpus offline (concurrent workers): per-sample seqlen
     AND an accumulate-type vocab histogram (the two-pass rarity recipe);
  2. train with a curriculum sampler that feeds easy (short) samples first
     and raises the difficulty cap on a schedule;
  3. batch by token budget (variable batch size) so short-sample phases
     pack more rows per step.

    python examples/data_efficiency_pretrain.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_model
from deepspeed_tpu.runtime.data_pipeline.curriculum import (
    CurriculumConfig, CurriculumScheduler, DeepSpeedDataSampler,
    VariableBatchConfig, batch_by_token_budget)
from deepspeed_tpu.runtime.data_pipeline.data_analyzer import (
    DataAnalyzer, load_difficulties, metric_seqlen, metric_total_vocab_freq,
    metric_vocab_histogram)

VOCAB, MAX_SEQ = 128, 64


def main() -> None:
    rng = np.random.RandomState(0)
    corpus = [{"input_ids": rng.randint(2, VOCAB, size=rng.randint(8, MAX_SEQ))}
              for _ in range(256)]
    workdir = tempfile.mkdtemp()

    # 1) offline analysis: concurrent map-reduce over 4 workers
    out = DataAnalyzer.run_map_reduce(
        corpus, save_path=workdir, num_workers=4,
        metric_names=["seqlen", "vocab"],
        metric_functions=[metric_seqlen, metric_vocab_histogram(VOCAB)],
        metric_types=["single_value_per_sample",
                      "accumulate_value_over_samples"])
    freq = out["vocab"]["accumulated"]
    rarity = metric_total_vocab_freq(freq)  # pass 2 uses the corpus stats
    print(f"analyzed {len(corpus)} samples; "
          f"median len {np.median(out['seqlen']['index_to_metric']):.0f}, "
          f"rarity(sample 0) {rarity(corpus[0]):.1f}")

    # 2) curriculum over the seqlen metric: fixed_root schedule raises the
    # cap from 16 toward MAX_SEQ over 90 steps (snapped to difficulty_step
    # increments: 16,16,16,24,24,... on the first steps)
    sched = CurriculumScheduler(CurriculumConfig(
        min_difficulty=16, max_difficulty=MAX_SEQ, schedule_type="fixed_root",
        total_curriculum_step=90))
    sampler = DeepSpeedDataSampler(
        load_difficulties(workdir, "seqlen"), sched, batch_size=64, seed=1)

    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_model("tiny", max_seq_len=MAX_SEQ, vocab_size=VOCAB,
                         attn_impl="xla"),
        config={"train_micro_batch_size_per_gpu": 1,  # x dp(8) = 8 rows
                "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
                "zero_optimization": {"stage": 1}})

    # 3) variable batch: the token budget decides how MANY rows this
    # curriculum step trains; rows run through the engine in fixed-shape
    # micro-batches of 8 (TPU programs are static — the variable part is
    # the number of micro-steps, the last one padded by repetition).  The
    # per-group LR multipliers are what a variable-LR schedule applies
    # (reference variable_batch_size_and_lr wraps the scheduler); wire
    # them into your optax schedule to scale lr with realized batch size.
    vb = VariableBatchConfig(max_tokens_per_batch=512)
    for step in range(6):
        sampler.set_step(step)
        idx = sampler.next_indices()
        lens = np.asarray([len(corpus[i]["input_ids"]) for i in idx])
        groups, lr_mults = batch_by_token_budget(lens, vb)
        cap = int(sched.get_difficulty(step))
        losses, n_rows = [], 0
        for grp in groups:  # EVERY packed group trains
            rows = [int(idx[j]) for j in grp]
            n_rows += len(rows)
            for lo in range(0, len(rows), 8):
                chunk = rows[lo:lo + 8]
                chunk = (chunk * 8)[:8]  # pad the tail by repetition
                ids = np.zeros((1, 8, cap), np.int32)
                for r, row in enumerate(chunk):
                    seq = corpus[row]["input_ids"][:cap]
                    ids[0, r, :len(seq)] = seq
                losses.append(float(engine.train_batch(
                    {"input_ids": jnp.asarray(ids)})))
        print(f"step {step}: cap {cap:3d}, {n_rows} rows in {len(groups)} "
              f"token-budget groups -> {len(losses)} micro-batches, vblr "
              f"mults {min(lr_mults):.2f}..{max(lr_mults):.2f}, "
              f"mean loss {np.mean(losses):.3f}")


if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    main()
