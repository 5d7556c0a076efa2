"""Tests: compressed comm, curriculum/data pipeline, compression, LoRA."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.compression.compress import (CompressionScheduler,
                                                fake_quantize, init_compression,
                                                prune_mask)
from deepspeed_tpu.linear.optimized_linear import (LoRAConfig, init_lora_linear,
                                                   lora_linear,
                                                   trainable_lora_params)
from deepspeed_tpu.parallel.mesh import DATA_AXIS, MeshTopology
from deepspeed_tpu.utils.jax_compat import shard_map
from deepspeed_tpu.runtime.comm.compressed import compressed_all_reduce
from deepspeed_tpu.runtime.config import MeshConfig
from deepspeed_tpu.runtime.data_pipeline.curriculum import (
    CurriculumConfig, CurriculumScheduler, VariableBatchConfig,
    apply_seqlen_curriculum, batch_by_token_budget)


def test_compressed_allreduce_error_feedback(devices8):
    topo = MeshTopology(MeshConfig(data=-1), devices8)

    def body(g, e):
        return compressed_all_reduce(g, e, DATA_AXIS)

    f = shard_map(body, check_vma=False, mesh=topo.mesh,
                  in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)),
                  out_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None)))
    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(8, 256).astype(np.float32))
    e = jnp.zeros_like(g)
    out, new_e = f(g, e)
    # each rank's result approximates the global mean of its own row? No:
    # pmean over data of per-rank rows -> all rows equal the mean
    expect = np.mean(np.asarray(g), axis=0)
    np.testing.assert_allclose(np.asarray(out)[0], expect, atol=0.05)
    # error feedback: residual is bounded by the quant step and nonzero
    assert float(jnp.max(jnp.abs(new_e))) < 0.1


def test_curriculum_linear_ladder():
    cfg = CurriculumConfig(enabled=True, min_difficulty=64, max_difficulty=512,
                           total_curriculum_step=100, difficulty_step=64)
    s = CurriculumScheduler(cfg)
    assert s.get_difficulty(0) == 64
    assert s.get_difficulty(100) == 512
    mid = s.get_difficulty(50)
    assert 64 <= mid <= 512 and mid % 64 == 0
    # ladder => few distinct shapes
    shapes = {s.get_difficulty(t) for t in range(100)}
    assert len(shapes) <= 8


def test_curriculum_discrete_and_truncation():
    cfg = CurriculumConfig(enabled=True, schedule_type="fixed_discrete",
                           difficulty=[32, 64, 128], max_step=[10, 20])
    s = CurriculumScheduler(cfg)
    assert s.get_difficulty(5) == 32
    assert s.get_difficulty(15) == 64
    assert s.get_difficulty(25) == 128
    batch = {"input_ids": jnp.ones((2, 128), jnp.int32)}
    out = apply_seqlen_curriculum(batch, 32)
    assert out["input_ids"].shape == (2, 32)


def test_variable_batch_token_budget():
    lens = np.array([100, 200, 300, 1000, 50, 60])
    batches, mults = batch_by_token_budget(lens, VariableBatchConfig(
        max_tokens_per_batch=600))
    covered = sorted(int(i) for b in batches for i in b)
    assert covered == list(range(6))
    for b in batches:
        max_len = max(int(lens[i]) for i in b)
        assert max_len * len(b) <= 600 or len(b) == 1
    assert len(mults) == len(batches)


def test_fake_quantize_ste_gradient():
    w = jnp.linspace(-1, 1, 64)
    g = jax.grad(lambda w: jnp.sum(fake_quantize(w, 4) ** 2))(w)
    assert np.all(np.isfinite(np.asarray(g)))
    q = fake_quantize(w, 4)
    assert len(np.unique(np.asarray(q).round(6))) <= 16


def test_prune_and_scheduler():
    params = {"layer": {"w": jnp.asarray(np.random.RandomState(0).randn(32, 32),
                                         jnp.float32),
                        "b": jnp.zeros(32)}}
    cfg = {"compression_training": {
        "sparse_pruning": {"shared_parameters": {"enabled": True, "ratio": 0.5,
                                                 "schedule_offset": 0}}}}
    out, sched = init_compression(params, cfg)
    w = np.asarray(out["layer"]["w"])
    assert (w == 0).mean() == pytest.approx(0.5, abs=0.05)
    # before offset nothing happens
    sched2 = CompressionScheduler({"sparse_pruning": {
        "shared_parameters": {"enabled": True, "ratio": 0.5,
                              "schedule_offset": 100}}})
    out2 = sched2.transform_params(params, global_step=0)
    assert (np.asarray(out2["layer"]["w"]) == 0).mean() < 0.1


def test_lora_linear_trains_only_adapters():
    lora = LoRAConfig(lora_r=4, lora_alpha=8)
    params = init_lora_linear(jax.random.PRNGKey(0), 16, 8, lora)
    x = jnp.ones((2, 16))

    def loss(p):
        return jnp.sum(lora_linear(p, x, lora) ** 2)

    g = jax.grad(loss)(params)
    assert float(jnp.max(jnp.abs(g["base"]))) == 0.0  # frozen
    # lora_b starts at zero so grad_a is zero at init; grad_b carries signal
    assert float(jnp.max(jnp.abs(g["lora_b"]))) > 0.0
    mask = trainable_lora_params(params)
    assert mask["lora_a"] and not mask["base"]


def test_lora_quantized_base():
    lora = LoRAConfig(lora_r=4)
    from deepspeed_tpu.linear.optimized_linear import QuantizationConfig

    params = init_lora_linear(jax.random.PRNGKey(0), 16, 8, lora,
                              quantize=QuantizationConfig())
    out = lora_linear(params, jnp.ones((2, 16)), lora)
    assert out.shape == (2, 8)


def test_structured_pruning_and_physical_clean():
    """Head + channel pruning masks whole structures during training, and
    redundancy_clean PHYSICALLY shrinks the arrays: the sliced model (new
    config) computes the same loss as the masked model (reference
    basic_layer.py head/channel pruning + redundancy_clean folding)."""
    import jax

    from deepspeed_tpu.compression.compress import redundancy_clean
    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.models.transformer import (causal_lm_loss,
                                                  init_transformer_params)

    cfg = llama_config("tiny", max_seq_len=16, attn_impl="xla")  # MHA tiny
    params = init_transformer_params(cfg, jax.random.PRNGKey(0))
    comp = {"compression_training": {
        "head_pruning": {"shared_parameters": {"enabled": True,
                                               "dense_ratio": 0.5}},
        "channel_pruning": {"shared_parameters": {"enabled": True,
                                                  "dense_ratio": 0.5}},
    }}
    masked, sched = init_compression(params, comp, n_heads=cfg.n_heads)
    # whole FFN channels went to zero
    up = np.asarray(masked["layers"]["mlp"]["w_up"])
    zero_cols = np.all(up == 0, axis=1)  # [L, F]
    assert (zero_cols.sum(-1) == cfg.ffn_size // 2).all()

    ids = {"input_ids": jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (2, 16)), jnp.int32)}
    masked_loss = float(causal_lm_loss(cfg, masked, ids, None))

    shrunk, new_cfg = redundancy_clean(params, sched, cfg)
    assert new_cfg.ffn_size == cfg.ffn_size // 2
    assert new_cfg.n_heads == cfg.n_heads // 2
    assert shrunk["layers"]["mlp"]["w_up"].shape[-1] == cfg.ffn_size // 2
    assert shrunk["layers"]["attn"]["wo"].shape[1] == \
        (cfg.n_heads // 2) * cfg.head_dim
    shrunk_loss = float(causal_lm_loss(new_cfg, shrunk, ids, None))
    np.testing.assert_allclose(shrunk_loss, masked_loss, rtol=1e-5)


def test_structured_pruning_respects_per_method_offsets():
    """head offset 0 / channel offset 1000: at step 0 only heads prune
    (code-review r3 finding)."""
    import jax

    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.models.transformer import init_transformer_params

    cfg = llama_config("tiny", max_seq_len=16)
    params = init_transformer_params(cfg, jax.random.PRNGKey(0))
    comp = {"compression_training": {
        "head_pruning": {"shared_parameters": {"enabled": True,
                                               "dense_ratio": 0.5,
                                               "schedule_offset": 0}},
        "channel_pruning": {"shared_parameters": {"enabled": True,
                                                  "dense_ratio": 0.5,
                                                  "schedule_offset": 1000}},
    }}
    masked, sched = init_compression(params, comp, n_heads=cfg.n_heads)
    up = np.asarray(masked["layers"]["mlp"]["w_up"])
    assert not np.any(np.all(up == 0, axis=1)), "channels pruned early"
    wo = np.asarray(masked["layers"]["attn"]["wo"])
    assert np.any(np.all(wo == 0, axis=2)), "heads not pruned at offset 0"
    # at step 1000, channels join
    masked2 = sched.transform_params(params, 1000, n_heads=cfg.n_heads)
    up2 = np.asarray(masked2["layers"]["mlp"]["w_up"])
    assert np.any(np.all(up2 == 0, axis=1))


def test_structured_pruning_non_transformer_degrades_gracefully():
    """Wrong layout: warn + disable, do NOT crash (code-review r3)."""
    params = {"w1": jnp.ones((8, 8)), "w2": jnp.ones((8, 4))}
    comp = {"compression_training": {
        "head_pruning": {"shared_parameters": {"enabled": True}}}}
    out, sched = init_compression(params, comp, n_heads=4)
    assert not sched.head_prune.enabled
    np.testing.assert_allclose(np.asarray(out["w1"]), np.ones((8, 8)))


def test_layer_reduction_student_init():
    """Reference student_initialization (compression/compress.py:192): the
    student's stacked layers are the teacher's configured layers; the
    embeddings/head come from the teacher; bad maps raise."""
    from deepspeed_tpu.compression.compress import init_compression
    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.models.transformer import init_transformer_params

    t_cfg = llama_config("tiny", max_seq_len=32)
    t_cfg.n_layers = 4
    s_cfg = llama_config("tiny", max_seq_len=32)
    s_cfg.n_layers = 2
    teacher = init_transformer_params(t_cfg, jax.random.PRNGKey(0))
    student = init_transformer_params(s_cfg, jax.random.PRNGKey(1))

    config = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_number_layer": 2, "teacher_layer": [1, 3]}}}
    out, _ = init_compression(student, config, teacher_params=teacher)

    np.testing.assert_array_equal(np.asarray(out["layers"]["attn"]["wq"]),
                                  np.asarray(teacher["layers"]["attn"]["wq"])[[1, 3]])
    np.testing.assert_array_equal(np.asarray(out["embed"]["tok"]),
                                  np.asarray(teacher["embed"]["tok"]))
    # bad layer map raises
    bad = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_number_layer": 2, "teacher_layer": [1, 9]}}}
    with pytest.raises(ValueError, match="out of range"):
        init_compression(student, bad, teacher_params=teacher)
    # wrong-depth student raises (3 layers vs keep 2)
    s3 = llama_config("tiny", max_seq_len=32)
    s3.n_layers = 3
    with pytest.raises(ValueError, match="shape mismatch"):
        init_compression(init_transformer_params(s3, jax.random.PRNGKey(2)),
                         config, teacher_params=teacher)


@pytest.mark.slow
def test_layer_reduction_student_beats_random_init():
    """A 2-layer student initialized from a trained 4-layer teacher starts
    at a lower loss than a randomly initialized 2-layer student (the point
    of the reference's student_initialization), and the KD loss against
    the teacher's logits is differentiable."""
    import deepspeed_tpu
    from deepspeed_tpu.compression.compress import (distillation_loss,
                                                    init_compression)
    from deepspeed_tpu.models.llama import llama_model

    teacher_model = llama_model("tiny", max_seq_len=32, n_layers=4)
    config = {"train_micro_batch_size_per_gpu": 8,
              "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
              "bf16": {"enabled": True}}
    engine, *_ = deepspeed_tpu.initialize(model=teacher_model, config=config)
    ids = np.random.RandomState(0).randint(0, 256, (1, 8, 32)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids)}
    for _ in range(25):
        engine.train_batch(batch)
    teacher = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                     engine.state.params)

    student_model = llama_model("tiny", max_seq_len=32, n_layers=2)
    random_student = student_model.init_params(jax.random.PRNGKey(7))
    kd_cfg = {"compression_training": {"layer_reduction": {
        "enabled": True, "keep_number_layer": 2, "teacher_layer": [0, 3]}}}
    distilled, _ = init_compression(random_student, kd_cfg,
                                    teacher_params=teacher)

    b0 = jax.tree_util.tree_map(lambda x: x[0], batch)
    l_rand = float(student_model.loss_fn(random_student, b0, None))
    l_dist = float(student_model.loss_fn(distilled, b0, None))
    assert l_dist < l_rand, (l_dist, l_rand)

    # KD loss: finite, positive, and grads vanish at logit equality
    r = np.random.RandomState(3)
    t_logits = jnp.asarray(r.randn(8, 32, 256).astype(np.float32))
    s_logits = jnp.asarray(r.randn(8, 32, 256).astype(np.float32))
    kd = distillation_loss(s_logits, t_logits, temperature=2.0)
    assert np.isfinite(float(kd)) and float(kd) > 0
    g = jax.grad(lambda s: distillation_loss(s, t_logits))(t_logits)
    assert float(jnp.max(jnp.abs(g))) < 1e-3  # cross-entropy min at s == t
