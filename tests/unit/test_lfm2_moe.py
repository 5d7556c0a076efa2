"""LFM2-MoE through the training path against the plain float32 reference
(``benchmark/reference/lfm2_moe_lm.py``): logits, loss and every gradient
leaf, for the benchmark's cut pattern and for the published 24-layer one; the
four expert shares against the uncut layer; the sigmoid router; what the
family refuses (ISSUE 32; the engine's part is test_lfm2_moe_engine.py); what
a recomputed block keeps and replays (ISSUE 42)."""

import collections
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import lfm2_moe_model
from deepspeed_tpu.models.lfm2_moe import SIZES, lfm2_moe_config
from deepspeed_tpu.models.layer_types import stack_runs
from deepspeed_tpu.models.transformer import (TransformerConfig, _ffn,
                                              logits_fn, transformer_forward)
from deepspeed_tpu.moe.sharded_moe import MoEConfig, _gate_and_aux
from deepspeed_tpu.ops.pallas import grouped_matmul as gmm_mod
from deepspeed_tpu.ops.pallas import moe_dispatch as rows_mod
from deepspeed_tpu.parallel.mesh import initialize_topology
from deepspeed_tpu.runtime.activation_checkpointing.checkpointing import (
    DEFAULT_POLICY, KERNEL_RESIDUALS)
from deepspeed_tpu.runtime.config import MeshConfig

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _bench(sub, name):
    spec = importlib.util.spec_from_file_location(
        f"bench_{name}", os.path.join(ROOT, "benchmark", sub, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _bench("reference", "lfm2_moe_lm")
CUT = ("conv", "full_attention", "conv", "conv", "conv")
PUBLISHED = SIZES["8b-a1b"][-1]
EXPERTS, HELD_FIRST, HELD = 8, 2, 4


def _desc(layer_types, dense, held_first=HELD_FIRST, held=HELD):
    h, nh, kvh, hd, dff, ew, vocab, experts, top_k, _, _ = SIZES["tiny"]
    return {"hidden_size": h, "num_attention_heads": nh,
            "num_key_value_heads": kvh, "head_dim": hd, "vocab_size": vocab,
            "norm_eps": 1e-5, "rope_theta": 1e6,
            "layer_types": list(layer_types), "num_dense_layers": dense,
            "conv_taps": 3, "experts_routed": experts,
            "experts_held": held, "experts_first": held_first,
            "num_experts_per_tok": top_k, "norm_topk_prob": True,
            "routed_scaling_factor": 1.0}


def _model(layer_types, dense, **over):
    over.setdefault("moe_held_first", HELD_FIRST)
    over.setdefault("moe_held_count", HELD)
    return lfm2_moe_model("tiny", max_seq_len=24, layer_types=layer_types,
                          dense_layers=dense, **over)


def test_the_published_pattern_is_the_catalogs():
    assert len(PUBLISHED) == 24
    assert [i for i, t in enumerate(PUBLISHED)
            if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    cfg = lfm2_moe_config("8b-a1b")
    assert cfg.dense_layers == 2 and cfg.dense_ffn_size == 7168
    runs = stack_runs(cfg)
    assert runs[0] == ("conv", "dense", 2) and runs[1] == ("attn", "experts", 1)
    assert sum(n for _, _, n in runs) == 24 and len(runs) == 13


@pytest.mark.parametrize("pattern,dense", [(CUT, 1), (PUBLISHED, 2)],
                         ids=["cut", "published24"])
def test_logits_loss_and_every_gradient_leaf_match_the_reference(pattern,
                                                                 dense):
    model = _model(pattern, dense)
    cfg = model.config
    params = jax.jit(model.init_params)(jax.random.PRNGKey(3))
    ids = np.random.default_rng(5).integers(0, cfg.vocab_size, (2, 24),
                                            dtype=np.int32)
    desc = _desc(pattern, dense)

    def loss_and_logits(p):
        hidden, _aux = transformer_forward(cfg, p, jnp.asarray(ids))
        return (model.loss_fn(p, jnp.asarray(ids), None),
                logits_fn(cfg, p, hidden))

    (loss, got), grads = jax.jit(jax.value_and_grad(
        loss_and_logits, has_aux=True))(params)
    for row, lg in zip(ids, np.asarray(got)):
        want, _ = REF.forward(desc, params, row)
        np.testing.assert_allclose(lg, np.asarray(want), atol=2e-5, rtol=1e-4)
    ref_loss, ref_grads, _ = REF.loss_and_grads(desc, params, ids)
    assert abs(float(loss) - ref_loss) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    ref_flat = jax.tree_util.tree_leaves(ref_grads)
    assert len(flat) == len(ref_flat)
    for (path, g), r in zip(flat, ref_flat):
        scale = max(float(np.abs(r).max()), 1e-6)
        assert float(np.abs(np.asarray(g) - r).max()) < 2e-4 * scale + 1e-7, \
            jax.tree_util.keystr(path)
    # the selection bias moves picks, never a weight: no gradient reaches it
    for run, (_, ffn, _) in zip(grads["layers"], stack_runs(cfg)):
        if ffn == "experts":
            assert not np.any(np.asarray(run["mlp"]["router_bias"]))


# ------------------------------------------- what a recomputed block keeps
KEEPS = {"default": {"remat": True},
         "nothing_saveable": {"remat": True,
                              "remat_policy": "nothing_saveable"},
         "no_remat": {"remat": False}}


def _kernel_sites(jaxpr, out=None):
    """{kernel: its ``pallas_call`` equations} over ``jaxpr`` and every
    jaxpr under it."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += 1
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _kernel_sites(sub, out)
    return out


@pytest.fixture(scope="module")
def kept():
    """{case of KEEPS: (every gradient leaf, the kernel sites of the
    gradient's jaxpr)} of the cut stack through the kernels, interpreted:
    ``impl="auto"`` as on the chip."""
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 256, (2, 24),
                                                        dtype=np.int32))
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gmm_mod, "on_tpu", lambda: True)
        mp.setattr(rows_mod, "on_tpu", lambda: True)
        mp.setattr(rows_mod, "rows_kernel_serves", lambda h, d, n: True)
        for case, over in KEEPS.items():
            model = _model(CUT, 1, attn_impl="flash", **over)
            params = jax.jit(model.init_params)(jax.random.PRNGKey(3))
            grad = jax.grad(lambda p, m=model: m.loss_fn(p, ids, None))
            out[case] = (jax.jit(grad)(params),
                         _kernel_sites(jax.make_jaxpr(grad)(params).jaxpr))
    return out


def test_remat_by_default_keeps_products_and_kernel_outputs():
    assert TransformerConfig().remat_policy == DEFAULT_POLICY
    assert set(KERNEL_RESIDUALS) == {"flash_out", "flash_lse",
                                     "grouped_matmul_out",
                                     "moe_dispatch_rows"}


@pytest.mark.parametrize("case", ["nothing_saveable", "no_remat"])
def test_what_a_block_keeps_changes_no_gradient_leaf(kept, case):
    got, _ = jax.tree_util.tree_flatten_with_path(kept["default"][0])
    want = jax.tree_util.tree_leaves(kept[case][0])
    assert len(got) == len(want)
    for (path, g), r in zip(got, want):
        scale = max(float(np.abs(r).max()), 1e-6)
        assert float(np.abs(np.asarray(g) - r).max()) < 2e-4 * scale + 1e-7, \
            jax.tree_util.keystr(path)


@pytest.mark.parametrize("case,replays,bodies", [
    ("default", 0, 4), ("no_remat", 0, 2), ("nothing_saveable", 1, 2)])
def test_a_block_that_keeps_its_kernels_outputs_replays_no_kernel(
        kept, case, replays, bodies):
    """Under the default a block's backward holds no second forward call of
    flash, the grouped matmul or the dispatch; under "nothing_saveable" it
    holds one of each.  ``bodies``: the expert layers traced — the run of
    three is one scanned body, and four layers where its blocks keep
    residuals (``UNROLLED_KEEPING_RUN``)."""
    sites = kept[case][1]
    assert (sites["dstpu_flash_bwd_dq"], sites["dstpu_flash_bwd_dkv"],
            sites["dstpu_grouped_matmul_dx"],
            sites["dstpu_grouped_matmul_dw"]) == (1, 1, 3 * bodies,
                                                  3 * bodies)
    assert sites["dstpu_flash_fwd"] == 1 + replays
    assert sites["dstpu_grouped_matmul"] == 3 * bodies * (1 + replays)
    # the dispatch kernel also lays d ys out (the combine's backward)
    assert sites["dstpu_moe_dispatch"] == bodies * (2 + replays)


def _expert_layer(held_first, held):
    """One expert layer's feed-forward part of the tiny model as a function
    of (its weights, its input), and the weights of the uncut layer."""
    h, *_ = SIZES["tiny"]
    cfg = lfm2_moe_config("tiny", moe_held_first=held_first,
                          moe_held_count=held)
    return cfg, lambda m, x: _ffn(cfg, {"mlp": m}, x)[0]


def test_the_four_shares_add_up_to_the_uncut_layer_in_output_and_gradients():
    h, _, _, _, _, ew, _, experts, _, _, _ = SIZES["tiny"]
    rng = np.random.default_rng(11)
    full = {"router": rng.standard_normal((h, experts)) * 0.5,
            "router_bias": rng.standard_normal((experts,)) * 0.05,
            "w_gate": rng.standard_normal((experts, h, ew)) / np.sqrt(h),
            "w_up": rng.standard_normal((experts, h, ew)) / np.sqrt(h),
            "w_down": rng.standard_normal((experts, ew, h)) / np.sqrt(ew)}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    x = jnp.asarray(rng.standard_normal((2, 24, h)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((2, 24, h)), jnp.float32)
    _, whole = _expert_layer(0, 0)
    y, vjp = jax.vjp(whole, full, x)
    g_full, gx_full = vjp(up)
    per = experts // 4
    y_sum, gx_sum, gr_sum = 0.0, 0.0, 0.0
    for r in range(4):
        _, part = _expert_layer(r * per, per)
        m = dict(full, **{k: full[k][r * per:(r + 1) * per]
                          for k in ("w_gate", "w_up", "w_down")})
        yr, vjp = jax.vjp(part, m, x)
        g, gx = vjp(up)
        y_sum, gx_sum, gr_sum = y_sum + yr, gx_sum + gx, gr_sum + g["router"]
        # each share's expert-weight gradients are the uncut layer's for
        # its experts
        for k in ("w_gate", "w_up", "w_down"):
            np.testing.assert_allclose(
                np.asarray(g[k]), np.asarray(g_full[k][r * per:(r + 1) * per]),
                atol=2e-5, rtol=1e-4)
    # nothing is computed alike on every chip inside the layer (no shared
    # expert): the shares' outputs, router and input gradients just add
    np.testing.assert_allclose(np.asarray(y_sum), np.asarray(y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(gx_sum), np.asarray(gx_full),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gr_sum),
                               np.asarray(g_full["router"]), atol=2e-5,
                               rtol=1e-4)


def test_sigmoid_router_bias_changes_a_pick_and_not_its_weight():
    cfg = MoEConfig(num_experts=4, top_k=2, scoring="sigmoid",
                    norm_topk=True)
    logits = jnp.asarray([[2.0, 1.0, 0.9, -3.0]])
    s = 1 / (1 + np.exp(-np.asarray(logits[0])))
    _, idx, w, aux = _gate_and_aux(logits, cfg)
    assert idx.tolist() == [[0, 1]] and float(aux) == 0.0 * 0 + float(aux)
    np.testing.assert_allclose(np.asarray(w[0]),
                               s[[0, 1]] / (s[0] + s[1] + 1e-6), rtol=1e-6)
    # a bias on expert 2 takes the second pick from expert 1; the weights
    # are the sigmoid scores of the picks, the bias nowhere in them
    bias = jnp.asarray([0.0, 0.0, 0.05, 0.0])
    _, idx_b, w_b, _ = _gate_and_aux(logits, cfg, bias=bias)
    assert idx_b.tolist() == [[0, 2]]
    np.testing.assert_allclose(np.asarray(w_b[0]),
                               s[[0, 2]] / (s[0] + s[2] + 1e-6), rtol=1e-6)
    # unnormalised and scaled
    raw = MoEConfig(num_experts=4, top_k=2, scoring="sigmoid",
                    norm_topk=False, routed_scale=2.5)
    np.testing.assert_allclose(
        np.asarray(_gate_and_aux(logits, raw, bias=bias)[2][0]),
        2.5 * s[[0, 2]], rtol=1e-6)


@pytest.mark.parametrize("what", ["serving", "mesh", "drop_tokens",
                                  "attention_mask"])
def test_what_lfm2_moe_refuses_by_name(what):
    if what == "serving":
        from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                                RaggedInferenceConfig)

        with pytest.raises(NotImplementedError, match="conv"):
            InferenceEngineV2(_model(CUT, 1), RaggedInferenceConfig(
                dtype="fp32", page_size=8, prefill_chunk=16, num_pages=32))
    elif what == "mesh":
        model = _model(CUT, 1)
        params = model.init_params(jax.random.PRNGKey(0))
        initialize_topology(MeshConfig(model=2), devices=jax.devices()[:2])
        try:
            with pytest.raises(NotImplementedError, match="model"):
                model.loss_fn(params, jnp.zeros((2, 24), jnp.int32), None)
        finally:
            initialize_topology(MeshConfig(data=1),
                                devices=jax.devices()[:1])
    elif what == "drop_tokens":
        model = _model(CUT, 1, moe_drop_tokens=True)
        params = model.init_params(jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError, match="moe_drop_tokens"):
            model.loss_fn(params, jnp.zeros((2, 24), jnp.int32), None)
    else:
        model = _model(CUT, 1)
        params = model.init_params(jax.random.PRNGKey(0))
        batch = {"input_ids": jnp.zeros((2, 24), jnp.int32),
                 "attention_mask": jnp.ones((2, 24), jnp.int32)}
        with pytest.raises(NotImplementedError, match="convolution"):
            model.loss_fn(params, batch, None)
