"""LFM2-MoE through ``deepspeed_tpu.initialize`` -> ``train_batch``: the
fused step's expert-share counters (``engine.moe_stats()``) against the
reference's picks, the selection bias left alone by AdamW, and a
Mixtral-style dropless model that now trains (ISSUE 32)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import mistral_model, mixtral_model
from deepspeed_tpu.parallel.mesh import initialize_topology
from deepspeed_tpu.runtime.config import MeshConfig

from test_lfm2_moe import CUT, HELD, REF, _desc, _model


def _engine(model, gas=2, **over):
    topo = initialize_topology(MeshConfig(data=1), devices=jax.devices()[:1])
    config = {
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": gas, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-2, "weight_decay": 0.1}},
        "mesh": {"data": 1}, "seed": 0, "steps_per_print": 10 ** 9}
    config.update(over)
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config,
                                          topology=topo)
    return engine


@pytest.mark.parametrize("gas", [1, 2])
def test_engine_trains_counts_and_leaves_the_bias_alone(gas):
    model = _model(CUT, 1)
    engine = _engine(model, gas)
    bias0 = [np.asarray(r["mlp"]["router_bias"])
             for r in engine.state.params["layers"][1:]]
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 256, (gas, 2, 24), dtype=np.int32)
    desc = _desc(CUT, 1)
    # the counters of the first step against the reference's picks on the
    # same weights as the step computed with (bf16-rounded)
    ref_picks = sum(np.asarray(REF.forward(desc, engine.state.params, row,
                                           round_to=jnp.bfloat16)[1])
                    for row in batch.reshape(-1, 24))
    losses = [float(engine.train_batch(batch))]
    st = engine.moe_stats()
    assert st["steps"] == 1 and st["calls"] == [gas] * 4
    picks = np.asarray(st["picks"])
    assert picks.shape == (4, HELD)
    # bf16 activations move a near-tie now and then
    assert np.abs(picks - ref_picks).sum() <= 0.02 * ref_picks.sum()
    assert all(r >= p for r, p in zip(st["rows_run"], picks.sum(axis=1)))
    assert all(g >= r for g, r in zip(st["rows_grid"], st["rows_run"]))
    for _ in range(5):
        losses.append(float(engine.train_batch(batch)))
    assert losses[-1] < losses[0] - 0.5
    st = engine.moe_stats()
    assert st["steps"] == 5 and st["calls"] == [5 * gas] * 4
    assert engine.moe_stats()["steps"] == 0
    for b0, run in zip(bias0, engine.state.params["layers"][1:]):
        assert np.array_equal(b0, np.asarray(run["mlp"]["router_bias"]))
    engine.close()


def test_a_dense_model_has_no_moe_stats():
    engine = _engine(mistral_model("tiny"), 1)
    assert engine.moe_stats() is None
    engine.close()


def test_mixtral_style_dropless_now_trains_and_its_loss_falls():
    model = mixtral_model("tiny", max_seq_len=24, moe_drop_tokens=False)
    engine = _engine(model, 1)
    batch = np.random.default_rng(2).integers(0, 256, (1, 2, 24),
                                              dtype=np.int32)
    losses = [float(engine.train_batch(batch)) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.5
    engine.close()


# sha256[:16] of ``engine._train_batch.lower(...).as_text()`` — the fused
# train step of the tiny dense models under bf16 / ZeRO-1 / AdamW / clip 1.0 —
# taken on the parent commit (2567555, jax 0.9.0) before PR 32 changed
# anything: typed stacks, the expert counters and the buffer mask must leave
# a homogeneous stack's step as it was (the two dense training cells'
# program).  A PR that means to change these programs takes the hashes anew
# from its own parent.
_PARENT_TRAIN_HLO = {
    ("mistral", 1): "353d6264bd218508",
    ("mistral", 2): "a29330c77b2b84b4",
    ("opt", 1): "76475a25753042b8",
    ("opt", 2): "8612c9ed5bd57997",
}


@pytest.mark.parametrize("family,gas", sorted(_PARENT_TRAIN_HLO))
def test_dense_train_steps_lower_as_before_typed_stacks(family, gas):
    import hashlib

    from deepspeed_tpu.models import opt_model

    model = {"mistral": mistral_model, "opt": opt_model}[family]("tiny")
    engine = _engine(model, gas, optimizer={"type": "AdamW",
                                            "params": {"lr": 1e-3}})
    batch = jax.ShapeDtypeStruct((gas, 2, 32), jnp.int32)
    with engine.topology.mesh:
        low = engine._train_batch.lower(engine.state, batch,
                                        jax.random.PRNGKey(0))
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    engine.close()
    assert got == _PARENT_TRAIN_HLO[(family, gas)]
