"""engine.compile() pass tests (reference: tests/unit/v1/compile, deepspeed/compile/)."""

import logging

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    DEFAULT_POLICY, POLICY_MAP, get_policy)
from tests.unit.simple_model import random_batch, simple_mlp_spec


def _engine(**cfg_extra):
    cfg = {
        "train_micro_batch_size_per_gpu": 4,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "gradient_clipping": 1.0,
    }
    cfg.update(cfg_extra)
    engine, *_ = deepspeed_tpu.initialize(model=simple_mlp_spec(), config=cfg)
    return engine


def test_compile_default_passes():
    engine = _engine()
    out = engine.compile()
    assert out is engine
    assert engine.is_compiled
    assert "zero3_compile" in engine.compile_passes_applied
    losses = [float(engine.train_batch(random_batch(batch_size=16, seed=i % 4, gas=1)))
              for i in range(10)]
    assert losses[-1] < losses[0]


def test_compile_unknown_pass_raises():
    engine = _engine()
    with pytest.raises(KeyError):
        engine.compile(passes=["not_a_pass"])
    with pytest.raises(ValueError):
        engine.compile(backend="tvm")


def test_compile_offload_adam_states_still_trains():
    engine = _engine()
    l0 = float(engine.train_batch(random_batch(batch_size=16, seed=0, gas=1)))
    engine.compile(passes=["offload_adam_states"])
    losses = [float(engine.train_batch(random_batch(batch_size=16, seed=i % 4, gas=1)))
              for i in range(10)]
    assert losses[-1] < l0


def test_compile_offload_activation_remat():
    from deepspeed_tpu.models.llama import llama_model

    model = llama_model("tiny", max_seq_len=32)
    assert not model.config.remat
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 5e-3}}})
    engine.compile(passes=["offload_activation"])
    assert model.config.remat
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, (1, 2, 32)).astype(np.int32)
    import jax.numpy as jnp

    batch = {"input_ids": jnp.asarray(ids)}
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert losses[-1] < losses[0]


# ------------------------------------------- what a recomputed block keeps
def _kept_grad(policy):
    import jax
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)
    f = lambda x: jnp.sum(jnp.tanh(x @ x.T) @ x)  # noqa: E731
    return (np.asarray(jax.grad(jax.checkpoint(f, policy=policy))(x)),
            np.asarray(jax.grad(f)(x)))


@pytest.mark.parametrize("name", sorted(POLICY_MAP))
def test_every_remat_policy_name_resolves_to_a_policy(name):
    """``remat_policy`` takes any name of ``POLICY_MAP``, the default's
    among them: each is something ``jax.checkpoint`` takes, and what is
    kept changes no gradient."""
    assert DEFAULT_POLICY in POLICY_MAP
    policy = get_policy(name)
    assert policy is None or callable(policy)
    got, want = _kept_grad(policy)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_an_unknown_remat_policy_warns_and_keeps_nothing(caplog):
    logger = logging.getLogger("DeepSpeedTPU")
    logger.propagate = True
    try:
        with caplog.at_level("WARNING", logger="DeepSpeedTPU"):
            assert get_policy("keep_what_i_mean") is None
    finally:
        logger.propagate = False
    assert any("unknown remat policy 'keep_what_i_mean'" in r.getMessage()
               for r in caplog.records)
