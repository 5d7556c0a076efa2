"""1-bit optimizers + checkpoint engine flavors (reference: tests/onebit/)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.runtime.fp16.onebit import (one_bit_adam, one_bit_lamb,
                                               zero_one_adam)
from tests.unit.simple_model import random_batch, simple_mlp_spec


# ---------------------------------------------------------------- 1-bit
def test_onebit_adam_warmup_matches_adamw():
    """During warmup (count <= freeze_step) OneBitAdam is exact AdamW."""
    import optax

    params = {"w": jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)}
    g = {"w": jnp.asarray(np.random.RandomState(1).randn(8, 8), jnp.float32)}
    ob = one_bit_adam(1e-2, freeze_step=10)
    ref = optax.adam(1e-2)
    s1, s2 = ob.init(params), ref.init(params)
    p1, p2 = params, params
    for _ in range(3):
        u1, s1 = ob.update(g, s1, p1)
        u2, s2 = ref.update(g, s2, p2)
        p1 = optax.apply_updates(p1, u1)
        p2 = optax.apply_updates(p2, u2)
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]),
                               rtol=1e-5, atol=1e-6)


def test_onebit_adam_freezes_variance():
    params = {"w": jnp.ones((4, 4), jnp.float32)}
    ob = one_bit_adam(1e-2, freeze_step=2)
    s = ob.init(params)
    rng = np.random.RandomState(2)
    for i in range(5):
        g = {"w": jnp.asarray(rng.randn(4, 4), jnp.float32)}
        _, s_next = ob.update(g, s, params)
        if i >= 2:  # past freeze: variance must not change
            np.testing.assert_array_equal(np.asarray(s.v["w"]),
                                          np.asarray(s_next.v["w"]))
        s = s_next


def test_zero_one_adam_refreshes_variance_on_interval():
    params = {"w": jnp.ones((4,), jnp.float32)}
    zo = zero_one_adam(1e-2, var_freeze_step=1, var_update_interval=3)
    s = zo.init(params)
    changed = []
    rng = np.random.RandomState(3)
    for i in range(7):
        g = {"w": jnp.asarray(rng.randn(4), jnp.float32)}
        _, s_next = zo.update(g, s, params)
        changed.append(not np.array_equal(np.asarray(s.v["w"]),
                                          np.asarray(s_next.v["w"])))
        s = s_next
    # step counts 1..7: warm at 1; refresh at 3 and 6
    assert changed == [True, False, True, False, False, True, False]


@pytest.mark.parametrize("opt_name,lr", [("OneBitAdam", 1e-2),
                                         ("ZeroOneAdam", 1e-2),
                                         ("OneBitLamb", 2e-3)])
def test_onebit_engine_trains(opt_name, lr):
    engine, *_ = deepspeed_tpu.initialize(
        model=simple_mlp_spec(),
        config={"train_micro_batch_size_per_gpu": 4,
                "optimizer": {"type": opt_name,
                              "params": {"lr": lr, "freeze_step": 3}},
                "gradient_clipping": 1.0})
    losses = [float(engine.train_batch(random_batch(batch_size=16, seed=i % 4, gas=1)))
              for i in range(16)]  # crosses the freeze boundary
    # batches cycle over 4 seeds: compare losses on the same batch
    assert losses[12] < losses[0]
    assert np.isfinite(losses).all()


def test_onebit_error_feedback_accumulates():
    params = {"w": jnp.zeros((256,), jnp.float32)}
    ob = one_bit_adam(1e-2, freeze_step=1)
    s = ob.init(params)
    g = {"w": jnp.asarray(np.random.RandomState(4).randn(256) * 1e-3,
                          jnp.float32)}
    _, s = ob.update(g, s, params)  # warmup step: no error
    assert float(jnp.abs(s.error["w"]).max()) == 0.0
    _, s = ob.update(g, s, params)  # compressed step: residual retained
    assert float(jnp.abs(s.error["w"]).max()) > 0.0


# ------------------------------------------------- checkpoint engine flavors
def test_nebula_datastates_engines(tmp_path):
    from deepspeed_tpu.runtime.checkpoint_engine.engines import (
        DataStatesCheckpointEngine, NebulaCheckpointEngine,
        make_checkpoint_engine)
    from deepspeed_tpu.runtime.config import DeepSpeedConfig

    for writer, cls in [("nebula", NebulaCheckpointEngine),
                        ("datastates", DataStatesCheckpointEngine)]:
        cfg = DeepSpeedConfig({"checkpoint": {"writer": writer}})
        eng = make_checkpoint_engine(cfg)
        assert isinstance(eng, cls)
        arrays = {"a": np.arange(8, dtype=np.float32)}
        path = str(tmp_path / f"{writer}.ckpt")
        eng.save(arrays, path)
        assert eng.commit("tag")
        got = eng.load(path)
        np.testing.assert_array_equal(got["a"], arrays["a"])


def test_onebit_weight_decay_requires_params():
    """params=None with weight_decay/LAMB must raise, not silently use grads
    as params (ADVICE r1 onebit.py:141)."""
    from deepspeed_tpu.runtime.fp16.onebit import one_bit_adam, one_bit_lamb

    g = {"w": jnp.ones((4,))}
    for opt in (one_bit_adam(1e-3, weight_decay=0.1), one_bit_lamb(1e-3)):
        state = opt.init(g)
        with pytest.raises(ValueError, match="needs params"):
            opt.update(g, state, None)
    # without decay/lamb, params=None stays fine
    opt = one_bit_adam(1e-3)
    state = opt.init(g)
    upd, _ = opt.update(g, state, None)
    assert jnp.all(jnp.isfinite(upd["w"]))
