"""Expert-parallel MoE dispatch (moe/ep_dispatch.py): the explicit
all-to-all shard_map path vs the SPMD einsum/sort path.

Reference behavior being pinned: expert compute runs behind an all-to-all
inside the expert-parallel group (deepspeed/moe/sharded_moe.py:96
``_AllToAll``) so expert-weight grads are BORN expert-sharded — the SPMD
formulation instead hits XLA's "involuntary full rematerialization" on
the expert-weight grad scatter under EP + ZeRO-2/3.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.moe.sharded_moe import MoEConfig, moe_ffn
from deepspeed_tpu.parallel.mesh import initialize_topology, reset_topology
from deepspeed_tpu.runtime.config import MeshConfig

B, S, H, F, E = 8, 4, 16, 24, 4


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(H, E).astype(np.float32) * 0.1)
    experts = {k: jnp.asarray(rng.randn(E, H, F).astype(np.float32) * 0.1)
               for k in ("w_gate", "w_up")}
    experts["w_down"] = jnp.asarray(rng.randn(E, F, H).astype(np.float32) * 0.1)
    return x, gate_w, experts


def _moe(cfg, **kw):
    """moe_ffn as ONE compiled program: called eagerly, the dispatch is
    hundreds of separately-compiled ops (45 s a test on the CPU tier)."""
    return jax.jit(lambda x, g, e: moe_ffn(x, g, e, cfg, **kw))


def _spmd_then_ep(cfg, devices, mesh_cfg=None):
    x, gate_w, experts = _inputs()
    reset_topology()
    out_s, aux_s = _moe(dataclasses.replace(cfg, ep_dispatch="spmd"))(
        x, gate_w, experts)
    initialize_topology(mesh_cfg or MeshConfig(expert=2, data=2), devices[:4])
    out_e, aux_e = _moe(cfg)(x, gate_w, experts)
    return out_s, aux_s, out_e, aux_e


def test_ep_dropless_matches_spmd_exactly(devices8):
    """Dropless routing is per-token deterministic: the all-to-all path
    must reproduce the SPMD path's output bit-for-bit (fp32 tolerance)."""
    cfg = MoEConfig(num_experts=E, top_k=2, drop_tokens=False)
    out_s, aux_s, out_e, aux_e = _spmd_then_ep(cfg, devices8)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_s),
                               rtol=1e-5, atol=1e-5)
    # aux: per-rank mean (reference multi-rank semantics) vs global
    # product-of-means — close on balanced data, not identical
    assert abs(float(aux_e) - float(aux_s)) < 0.3 * abs(float(aux_s)) + 1e-4


def test_ep_capacity_matches_spmd_when_nothing_drops(devices8):
    """With capacity ample enough that NO token drops under either the
    global or the per-rank position count, the two capacity paths agree."""
    cfg = MoEConfig(num_experts=E, top_k=2, drop_tokens=True,
                    capacity_factor=float(E))  # cap >= T*K per rank
    out_s, _, out_e, _ = _spmd_then_ep(cfg, devices8)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_s),
                               rtol=1e-5, atol=1e-5)


def test_ep_gelu_no_wgate(devices8):
    """Non-swiglu experts (no w_gate) ride the same dispatch."""
    x, gate_w, experts = _inputs()
    experts = {k: experts[k] for k in ("w_up", "w_down")}
    cfg = MoEConfig(num_experts=E, top_k=1, drop_tokens=False)
    reset_topology()
    out_s, _ = _moe(dataclasses.replace(cfg, ep_dispatch="spmd"),
                    activation="gelu")(x, gate_w, experts)
    initialize_topology(MeshConfig(expert=2, data=2), devices8[:4])
    out_e, _ = _moe(cfg, activation="gelu")(x, gate_w, experts)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_s),
                               rtol=1e-5, atol=1e-5)


def test_ep_grads_match_and_born_expert_sharded(devices8):
    """The deliverable: expert-weight grads through the EP path (a) equal
    the SPMD path's grads and (b) come out of the compiled program already
    sharded over the expert axis, with the dispatch pinned as all-to-all
    in the HLO — no partitioner-driven resharding of the cotangent."""
    x, gate_w, experts = _inputs()
    cfg = MoEConfig(num_experts=E, top_k=2, drop_tokens=False)

    def loss(ex, mode):
        o, _ = moe_ffn(x, gate_w, ex,
                       dataclasses.replace(cfg, ep_dispatch=mode))
        return jnp.sum(o * o)

    reset_topology()
    g_spmd = jax.grad(lambda ex: loss(ex, "spmd"))(experts)

    topo = initialize_topology(MeshConfig(expert=2, data=2), devices8[:4])
    ex_sharded = {
        k: jax.device_put(v, NamedSharding(topo.mesh, P("expert", None, None)))
        for k, v in experts.items()}
    gf = jax.jit(jax.grad(lambda ex: loss(ex, "auto")))
    g_ep = gf(ex_sharded)
    for k in g_spmd:
        np.testing.assert_allclose(np.asarray(g_ep[k]), np.asarray(g_spmd[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)
        spec_axes = [a for s in g_ep[k].sharding.spec if s
                     for a in (s if isinstance(s, tuple) else (s,))]
        assert "expert" in spec_axes, (k, g_ep[k].sharding)
    hlo = gf.lower(ex_sharded).compile().as_text()
    assert "all-to-all" in hlo, "EP dispatch not lowered to all-to-all"


@pytest.mark.slow
def test_ep_dropless_stage2_no_involuntary_remat(devices8, capfd):
    """End-to-end: dropless mixtral, expert2 x data4, ZeRO-2 — the exact
    composition that used to trigger XLA's 'Involuntary full
    rematerialization' on the expert-weight grad scatter.  The EP
    all-to-all path must compile clean and train."""
    import deepspeed_tpu
    from deepspeed_tpu.models import mixtral_model

    model = mixtral_model("tiny", max_seq_len=32, moe_drop_tokens=False)
    config = {"train_micro_batch_size_per_gpu": 8,
              "optimizer": {"type": "Adam", "params": {"lr": 5e-3}},
              "bf16": {"enabled": True},
              "mesh": {"expert": 2, "data": -1},
              "zero_optimization": {"stage": 2}}
    engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
    ids = np.random.RandomState(0).randint(0, 256, (1, 8, 32)).astype(np.int32)
    batch = {"input_ids": jnp.asarray(ids)}
    losses = [float(engine.train_batch(batch)) for _ in range(5)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    err = capfd.readouterr().err
    assert "Involuntary full rematerialization" not in err


def test_ep_uneven_tp_ffn_falls_back_to_spmd(devices8):
    """EP + TP with an FFN dim that does not divide the model axis must
    fall back to the SPMD path (GSPMD handles uneven shardings) instead of
    failing shard_map spec validation."""
    rng = np.random.RandomState(2)
    Fo = 25  # not divisible by model=2
    x = jnp.asarray(rng.randn(B, S, H).astype(np.float32))
    gate_w = jnp.asarray(rng.randn(H, E).astype(np.float32) * 0.1)
    experts = {k: jnp.asarray(rng.randn(E, H, Fo).astype(np.float32) * 0.1)
               for k in ("w_gate", "w_up")}
    experts["w_down"] = jnp.asarray(rng.randn(E, Fo, H).astype(np.float32) * 0.1)
    cfg = MoEConfig(num_experts=E, top_k=2, drop_tokens=False)
    reset_topology()
    out_s, _ = moe_ffn(x, gate_w, experts,
                       dataclasses.replace(cfg, ep_dispatch="spmd"))
    initialize_topology(MeshConfig(expert=2, data=2, model=2), devices8)
    out_e, _ = moe_ffn(x, gate_w, experts, cfg)  # must not raise
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_s),
                               rtol=1e-5, atol=1e-5)
