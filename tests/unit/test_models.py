"""Model family tests: train each family end-to-end on the CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import (bert_model, gpt2_model, llama_model,
                                  mixtral_model)

#: the training tests below are the multi-minute integration tier; the two
#: FLOP-count tests at the end are arithmetic and run in tier-1
slow = pytest.mark.slow

SEQ = 32
BS = 4


def _lm_batch(vocab, seed=0, gas=1, bs=BS):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, vocab, size=(gas, bs, SEQ)).astype(np.int32)
    return {"input_ids": jnp.asarray(ids)}


def _train(model, cfg_overrides=None, steps=6, vocab=256, batch_fn=_lm_batch):
    config = {
        "train_micro_batch_size_per_gpu": BS,
        "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
        "bf16": {"enabled": True},
    }
    config.update(cfg_overrides or {})
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
    losses = []
    for i in range(steps):
        losses.append(float(engine.train_batch(batch_fn(vocab, seed=0))))
    assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], f"no learning: {losses}"
    return engine, losses


@slow
def test_llama_tiny_trains():
    _train(llama_model("tiny", max_seq_len=SEQ))


@slow
def test_llama_gqa_shapes():
    model = llama_model("tiny", max_seq_len=SEQ, n_kv_heads=2)
    _train(model)


@slow
def test_gpt2_tiny_trains():
    _train(gpt2_model("tiny"))


@slow
def test_bert_tiny_trains():
    def mlm_batch(vocab, seed=0, gas=1):
        rng = np.random.RandomState(seed)
        ids = rng.randint(0, vocab, size=(gas, BS, SEQ)).astype(np.int32)
        labels = np.where(rng.rand(gas, BS, SEQ) < 0.15, ids, -100).astype(np.int32)
        return {"input_ids": jnp.asarray(ids), "labels": jnp.asarray(labels)}

    _train(bert_model("tiny"), batch_fn=mlm_batch)


@slow
def test_mixtral_tiny_trains():
    _train(mixtral_model("tiny", max_seq_len=SEQ))


@slow
def test_llama_zero3_tp_mesh(devices8):
    """2-way TP x 4-way ZeRO-3: the composition milestone."""
    model = llama_model("tiny", max_seq_len=SEQ)
    engine, _ = _train(model, {"mesh": {"model": 2, "data": -1},
                               "zero_optimization": {"stage": 3}})
    # check a TP-ruled param is sharded over model axis AND a zero axis
    wq = engine.state.params["layers"]["attn"]["wq"]
    flat_axes = [a for s in wq.sharding.spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert "model" in flat_axes
    assert "data" in flat_axes


@slow
def test_mixtral_expert_parallel(devices8):
    model = mixtral_model("tiny", max_seq_len=SEQ)
    engine, _ = _train(model, {"mesh": {"expert": 4, "data": -1},
                               "zero_optimization": {"stage": 2}})
    w = engine.state.params["layers"]["mlp"]["w_up"]
    flat_axes = [a for s in w.sharding.spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert "expert" in flat_axes


@slow
def test_remat_trains():
    _train(llama_model("tiny", max_seq_len=SEQ, remat=True))


@slow
def test_unscanned_matches_scanned():
    m1 = llama_model("tiny", max_seq_len=SEQ, scan_layers=True)
    m2 = llama_model("tiny", max_seq_len=SEQ, scan_layers=False)
    rng = jax.random.PRNGKey(0)
    p1 = m1.init_params(rng)
    p2 = m2.init_params(rng)
    batch = jax.tree_util.tree_map(lambda x: x[0], _lm_batch(256))
    l1 = m1.loss_fn(p1, batch, None)
    l2 = m2.loss_fn(p2, batch, None)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


@slow
def test_tiled_loss_matches_full():
    m_full = llama_model("tiny", max_seq_len=SEQ, attn_impl="xla")
    m_tiled = llama_model("tiny", max_seq_len=SEQ, attn_impl="xla", loss_chunk=8)
    # SEQ-1=31 not divisible by 8 -> pad seq to 33 so hidden[:, :-1] is 32
    import numpy as np
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 33)), jnp.int32)
    p = m_full.init_params(jax.random.PRNGKey(0))
    l1 = m_full.loss_fn(p, {"input_ids": ids}, None)
    l2 = m_tiled.loss_fn(p, {"input_ids": ids}, None)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    g1 = jax.grad(lambda p: m_full.loss_fn(p, {"input_ids": ids}, None))(p)
    g2 = jax.grad(lambda p: m_tiled.loss_fn(p, {"input_ids": ids}, None))(p)
    a = jax.tree_util.tree_leaves(g1)
    b = jax.tree_util.tree_leaves(g2)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5, rtol=1e-3)


@slow
def test_mics_mesh_and_sharding(devices8):
    import deepspeed_tpu
    model = llama_model("tiny", max_seq_len=SEQ, attn_impl="xla")
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "mics_shard_size": 4}})
    topo = engine.topology
    assert topo.axis_size("data") == 4
    assert topo.axis_size("repl") == 2
    # params sharded over data (4-way), replicated over repl
    wq = engine.state.params["layers"]["attn"]["wq"]
    axes = [a for s in wq.sharding.spec if s for a in (s if isinstance(s, tuple) else (s,))]
    assert "data" in axes and "repl" not in axes
    # trains
    ids = np.random.RandomState(0).randint(0, 256, (1, 8, SEQ)).astype(np.int32)
    loss = engine.train_batch({"input_ids": jnp.asarray(ids)})
    assert np.isfinite(float(loss))


def test_flops_per_token_counts_active_experts_only():
    """MFU denominator: a mixtral layer prices top_k experts + router, not
    all experts (total-param pricing would overstate MoE MFU 4x at 8x/top2)."""
    from deepspeed_tpu.models.mixtral import mixtral_config
    from deepspeed_tpu.models.llama import llama_config
    from deepspeed_tpu.models.transformer import flops_per_token

    moe = mixtral_config("8x160m", max_seq_len=1024)
    dense = llama_config("160m", max_seq_len=1024)
    f_moe = flops_per_token(moe, 1024)
    f_dense = flops_per_token(dense, 1024)
    # same trunk; MoE adds (top_k - 1) extra expert MLPs + router per layer
    mlp = moe.hidden_size * moe.ffn_size * 3
    expect_extra = 6.0 * moe.n_layers * (
        (moe.moe_top_k - 1) * mlp + moe.hidden_size * moe.moe_experts)
    np.testing.assert_allclose(f_moe - f_dense, expect_extra, rtol=1e-6)
    # and nowhere near total-expert pricing
    assert f_moe < f_dense + 6.0 * moe.n_layers * 3 * mlp


def _benchmark_dense_cases():
    """(family module, sizes, sequence) for each dense configuration of
    BENCHMARK.json, at the sequence its cell runs: a training cell's
    ``sequence_length``, a serving configuration's ``page_size *
    max_pages_per_seq``."""
    import importlib
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cases = []
    for entry in bench["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            sizes = json.load(f)
        if sizes["family"] not in ("opt", "mistral"):
            continue  # the MoE / linear-attention share has its own count
        cell = next(w for w in bench["workloads"]
                    if w["config"] == entry["name"])
        with open(os.path.join(root, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        seq = traffic.get("sequence_length") or (
            sizes["engine"]["page_size"] * sizes["engine"]["max_pages_per_seq"])
        family = importlib.import_module(
            "benchmark.families." + sizes["family"])
        cases.append(pytest.param(family, sizes, int(seq), id=entry["name"]))
    return cases


@pytest.mark.parametrize("family,sizes,seq", _benchmark_dense_cases())
def test_flops_per_token_agrees_with_the_benchmarks_count(family, sizes, seq):
    """One FLOP count: the engine's MFU gauge prices a token as the
    benchmark's ``train_mfu_pct`` does (benchmark/roofline.py's rules), at
    the published widths of each dense benchmark configuration."""
    from benchmark import roofline
    from deepspeed_tpu.models.transformer import flops_per_token

    n_layers = int(sizes["num_hidden_layers"])
    model = family.build(sizes, n_layers, seq, jnp.float32)
    want = roofline.train_flops_per_token(family.describe(sizes), n_layers,
                                          seq)
    np.testing.assert_allclose(flops_per_token(model.config, seq), want,
                               rtol=1e-6)
    np.testing.assert_allclose(model.flops_per_sample, want * seq, rtol=1e-6)
