"""Model family tests: mistral/qwen/phi/opt/falcon (reference:
inference/v2/model_implementations/*, module_inject/containers/*)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import (bloom_model, falcon_model,
                                  gpt_neox_model, mistral_model, opt_model,
                                  phi_model, qwen_model)

SEQ = 32
FAMILIES = [mistral_model, qwen_model, phi_model, opt_model,
            falcon_model, bloom_model, gpt_neox_model]


def _batch(vocab, seed=0, bs=2):
    rng = np.random.RandomState(seed)
    return {"input_ids": jnp.asarray(
        rng.randint(0, vocab, (1, bs, SEQ)), jnp.int32)}


@pytest.mark.parametrize("family", FAMILIES, ids=lambda f: f.__name__)
def test_family_trains(family):
    model = family("tiny", max_seq_len=SEQ)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}},
                "zero_optimization": {"stage": 1}})
    b = _batch(model.config.vocab_size)
    losses = [float(engine.train_batch(b)) for _ in range(8)]
    assert losses[-1] < losses[0], losses


def test_family_structure_flags():
    assert qwen_model("tiny").config.qkv_bias
    assert not qwen_model("tiny").config.use_bias
    assert phi_model("tiny").config.parallel_block
    assert phi_model("tiny").config.rotary_pct == 0.4
    assert opt_model("tiny").config.activation == "relu"
    assert falcon_model("tiny").config.kv_heads == 1  # multi-query
    assert mistral_model("tiny").config.kv_heads == 2  # GQA


@pytest.mark.parametrize("family", [phi_model, falcon_model, qwen_model,
                                    gpt_neox_model],
                         ids=lambda f: f.__name__)
def test_family_paged_inference_matches_dense(family):
    """The paged (inference v2) path must agree with the dense cached
    decode for the structural variants (parallel block, partial rotary,
    qkv bias, multi-query)."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from tests.unit.test_inference_v2 import _dense_greedy

    model = family("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = list(np.random.RandomState(3).randint(0, model.config.vocab_size, 11))
    want = _dense_greedy(model, params, prompt, 6)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
        max_pages_per_seq=8), params=params)
    got = eng.generate_all([RaggedRequest(prompt_ids=prompt, max_new_tokens=6)])
    assert got[0] == want


def test_partial_rotary_only_rotates_prefix():
    from deepspeed_tpu.models.transformer import _rope

    x = jnp.asarray(np.random.RandomState(0).randn(1, 4, 2, 8), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(4), (1, 4))
    full = _rope(x, 10000.0, pos, pct=1.0)
    part = _rope(x, 10000.0, pos, pct=0.5)
    # pass-through tail unchanged
    np.testing.assert_array_equal(np.asarray(part[..., 4:]),
                                  np.asarray(x[..., 4:]))
    assert not np.allclose(np.asarray(part[..., :4]), np.asarray(x[..., :4]))
    assert not np.allclose(np.asarray(full), np.asarray(part))


def test_parallel_block_shares_single_norm():
    """falcon/phi parallel blocks carry ONE shared input layernorm (no
    norm2), matching the real architectures (ADVICE r1 families.py)."""
    import jax

    from deepspeed_tpu.models.families import falcon_model, phi_model

    for fam in (falcon_model, phi_model):
        model = fam("tiny", max_seq_len=64)
        params = model.init_params(jax.random.PRNGKey(0))
        assert "norm2" not in params["layers"], fam.__name__
        loss = model.loss_fn(
            params, {"input_ids": jnp.zeros((2, 16), jnp.int32)}, None)
        assert jnp.isfinite(loss)


def test_alibi_distance_penalty_and_v1_decode():
    """ALiBi (bloom): more distant keys get linearly more negative scores
    per-head; dense cached decode (v1 path) matches the full forward."""
    from deepspeed_tpu.models.transformer import (alibi_slopes,
                                                  forward_with_cache,
                                                  logits_fn,
                                                  transformer_forward)

    s = np.asarray(alibi_slopes(4))
    assert (s > 0).all() and (np.diff(s) < 0).all()  # decreasing, positive
    s8 = np.asarray(alibi_slopes(8))
    np.testing.assert_allclose(s8[0], 2 ** -1.0, rtol=1e-6)

    model = bloom_model("tiny", max_seq_len=64)
    cfg = model.config
    params = model.init_params(jax.random.PRNGKey(0))
    ids = np.random.RandomState(4).randint(0, 256, (2, 12)).astype(np.int32)
    hidden, _ = transformer_forward(cfg, params, jnp.asarray(ids))
    full = np.asarray(logits_fn(cfg, params, hidden), np.float32)

    import dataclasses

    from deepspeed_tpu.models.transformer import init_kv_cache
    cache = init_kv_cache(cfg, 2, 32, jnp.float32)
    step, cache = forward_with_cache(cfg, params, jnp.asarray(ids), cache,
                                     jnp.zeros((2,), jnp.int32))
    np.testing.assert_allclose(np.asarray(step, np.float32), full,
                               atol=2e-4, rtol=2e-3)


def test_bloom_paged_inference_matches_dense(monkeypatch):
    """ALiBi through the v2 paged engine: whole-prompt and chunked
    prefill, XLA fallback AND Pallas kernels (interpret mode), must all
    reproduce the dense cached decode."""
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig,
                                            RaggedRequest)
    from tests.unit.test_inference_v2 import _dense_greedy

    model = bloom_model("tiny", max_seq_len=256)
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = list(np.random.RandomState(8).randint(
        0, model.config.vocab_size, 21))
    want = _dense_greedy(model, params, prompt, 6)

    for kernel in ("0", "1"):
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", kernel)
        # quant rides along so the kernel's alibi+int8 operand ordering
        # (slopes popped from *rest before the scales) stays covered
        for chunk, quant in ((0, False), (16, False), (0, True), (16, True)):
            eng = InferenceEngineV2(model, RaggedInferenceConfig(
                dtype="fp32", page_size=8, num_pages=32, max_seqs=2,
                max_pages_per_seq=8, prefill_chunk=chunk,
                kv_quant=quant), params=params)
            got = eng.generate_all(
                [RaggedRequest(prompt_ids=prompt, max_new_tokens=6)])
            assert got[0] == want, (kernel, chunk, quant, got[0], want)


@pytest.mark.parametrize("name, why", [
    ("mistral4", "'mla' has no mix"),
    ("xing4", "'mla' has no mix"),
    ("evabyte", "the backward of a window-plus-summaries attention"),
])
def test_served_only_families_are_listed_and_refuse_training_by_name(name,
                                                                     why):
    """A family with layer types of its own is listed with its two entry
    points (``<name>_config``, ``<name>_model``) in ``deepspeed_tpu.models``
    and named in ``models/families.py``; its training entry refuses with the
    family's name and the reason."""
    import deepspeed_tpu.models as models
    from deepspeed_tpu.models import families

    assert {f"{name}_config", f"{name}_model"} <= set(models.__all__)
    assert f"``{name}.py``" in families.__doc__
    model = getattr(models, f"{name}_model")("tiny")
    assert getattr(models, f"{name}_config")("tiny").n_layers == \
        model.config.n_layers
    for entry in (model.loss_fn, model.apply_fn):
        with pytest.raises(NotImplementedError,
                           match=f"{name} is served only") as err:
            entry(None, {"input_ids": jnp.zeros((1, 4), jnp.int32)}, None)
        assert why in str(err.value)
    # and the generic training forward refuses a residual of several streams
    if model.config.hc_mult > 1:
        assert "a residual of several streams" in str(err.value)
