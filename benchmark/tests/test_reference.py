"""The float32 reference against the program at tiny widths on the CPU:
both block variants, the loss, and prefill-then-decode through the paged
engine."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest as manifest_mod
from benchmark.reference import dense_lm

CASES = {"opt": "opt-6.7b-train", "mistral": "mistral-7b-serve"}


def _tiny(family_name):
    man = manifest_mod.Manifest()
    cfg = man.config(CASES[family_name])
    sizes = dict(cfg, **cfg["tiny"])
    family = man.module("families", cfg["family"])
    return man, cfg, sizes, family


@pytest.mark.parametrize("family_name", sorted(CASES))
def test_loss_matches_the_programs_loss(family_name):
    from deepspeed_tpu.models.transformer import causal_lm_loss

    _, _, sizes, family = _tiny(family_name)
    desc = family.describe(sizes)
    model = family.build(sizes, sizes["num_hidden_layers"], 32, jnp.float32)
    params = model.init_params(jax.random.PRNGKey(3))
    # biases and norm offsets are zero at init: make them count
    params = jax.tree_util.tree_map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.PRNGKey(a.size % 97),
                                               a.shape, a.dtype), params)
    ids = np.random.default_rng(0).integers(0, desc["vocab_size"], (3, 32),
                                            dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        want = float(causal_lm_loss(model.config, params, jnp.asarray(ids)))
    got = dense_lm.loss(desc, params, ids)
    assert abs(got - want) < 2e-5, (got, want)


def _tiny_engine(family_name, seed):
    from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                            RaggedInferenceConfig)

    man, cfg, sizes, family = _tiny(family_name)
    serve = man.config("mistral-7b-serve")
    ecfg = dict(serve["engine"], **serve["tiny_engine"])
    desc = family.describe(sizes)
    model = family.build(sizes, sizes["num_hidden_layers"],
                         min(ecfg["page_size"] * ecfg["max_pages_per_seq"],
                             sizes["max_position_embeddings"]), jnp.float32)
    params = model.init_params(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.01 * jax.random.normal(jax.random.PRNGKey(a.size % 89),
                                               a.shape, a.dtype), params)
    engine = InferenceEngineV2(model, RaggedInferenceConfig(**ecfg),
                               params=params)
    return man, desc, engine


def _program_logits(engine, ids, steps):
    """Last-position logits after the engine's chunked prefill and after
    each of ``steps`` greedy decode steps through its paged cache: the
    model-runner programs called directly, because the engine's public
    surface returns tokens only.  (The benchmark's own check goes through
    ``put`` / ``step``; this is the tighter comparison a test can afford.)"""
    from deepspeed_tpu.inference.v2.model_runner import paged_decode

    ps, chunk = engine.block.page_size, engine._chunk
    n, trash = len(ids), engine.block.trash_page
    assert n <= chunk
    pages = engine.allocator.alloc(-(-(n + steps) // ps))
    buf = np.zeros((chunk,), np.int32)
    buf[:n] = ids
    rows = np.full((chunk // ps,), trash, np.int32)
    npg = -(-n // ps)
    rows[:npg] = pages[:npg]
    b = 1
    while b < npg:
        b *= 2
    prev = np.full((min(b, engine.block.max_pages_per_seq),), trash, np.int32)
    prev[:npg] = pages[:npg]
    logits, engine._pools = engine._prefill_chunk(
        engine.params, engine._pools, jnp.asarray(buf), jnp.asarray(rows),
        jnp.asarray(prev), jnp.int32(0), jnp.int32(n))
    got, ids = [np.asarray(logits, np.float32)], list(ids)
    decode = jax.jit(lambda *a: paged_decode(engine.cfg, *a),
                     donate_argnums=(1,))
    B = engine.block.max_seqs
    table = np.full((B, engine.block.max_pages_per_seq), trash, np.int32)
    table[0, :len(pages)] = pages
    act = np.zeros((B,), bool)
    act[0] = True
    for _ in range(steps):
        ids.append(int(np.argmax(got[-1])))
        last, pos = np.zeros((B,), np.int32), np.zeros((B,), np.int32)
        last[0], pos[0] = ids[-1], len(ids) - 1
        lg, engine._pools = decode(
            engine.params, engine._pools, jnp.asarray(last), jnp.asarray(pos),
            jnp.asarray(table), jnp.asarray(act))
        got.append(np.asarray(lg[0], np.float32))
    engine.allocator.free(pages)
    return got, ids


@pytest.mark.parametrize("family_name", sorted(CASES))
def test_prefill_then_decode_logits_match_through_the_paged_cache(family_name):
    _, desc, engine = _tiny_engine(family_name, 4)
    ids = np.random.default_rng(12).integers(
        0, desc["vocab_size"], 14, dtype=np.int64).tolist()
    got, ids = _program_logits(engine, ids, 4)
    engine.assert_no_leaks()
    ref = np.asarray(dense_lm.logits(desc, engine.params, ids))[14 - 1:]
    engine.close()
    scale = float(np.max(np.abs(ref)))
    assert len(got) == 5
    for g, r in zip(got, ref):
        assert float(np.max(np.abs(g - r))) / scale < 1e-4
        assert int(np.argmax(g)) == int(np.argmax(r))


@pytest.mark.parametrize("family_name", sorted(CASES))
def test_the_put_step_check_holds_greedy_tokens_to_the_reference(family_name):
    man, desc, engine = _tiny_engine(family_name, 5)
    gen = man.module("generators", "serve_requests")
    ctx = types.SimpleNamespace(
        seed=11, traffic={"check_prompt_tokens": [14, 9],
                          "check_decode_steps": 4})
    out = gen.check_against_reference(ctx, engine, desc, desc["vocab_size"])
    engine.assert_no_leaks()
    assert out["positions"] == 10 and out["prompt_tokens"] == [14, 9]
    # float32 on the CPU: the engine's greedy token is the reference's argmax
    assert out["argmax_agree"] == 10 and out["max_regret"] == 0.0

    # a program whose arithmetic is another model's is caught: the same
    # engine held to a reference that reads other weights
    class OtherWeights:
        def __init__(self, engine, params):
            self._engine, self.params = engine, params

        def __getattr__(self, name):
            return getattr(self._engine, name)

    other = jax.tree_util.tree_map(
        lambda a: a + 0.5 * jax.random.normal(jax.random.PRNGKey(a.size % 83),
                                              a.shape, a.dtype), engine.params)
    bad = gen.check_against_reference(ctx, OtherWeights(engine, other), desc,
                                      desc["vocab_size"])
    engine.close()
    assert bad["argmax_agree"] < 5 and bad["max_regret"] > 0.1, bad
