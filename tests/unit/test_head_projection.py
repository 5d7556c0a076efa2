"""A head projection reads its weight as it is stored (ISSUE 61): the paged
programs compute ``h @ w`` as a plain product behind an optimization barrier
and only then view it by head (``transformer.head_projection(pinned=True)``
through ``model_runner._by_head`` / ``model_runner.attn_qkv``), so that XLA:TPU
cannot fold the view into the product and copy the weight head-major on every
call.  Here, on the CPU: the pin is identity arithmetic, the training step
holds none, and a serving program holds one a product.  What the pin does to
the compiled program is ``tests/unit/test_sdar_aot.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,
                                        RaggedInferenceConfig)
from deepspeed_tpu.inference.v2 import model_runner as mr
from deepspeed_tpu.models import (mimo_v2_model, mistral_model, opt_model,
                                  phi_model, qwen_model)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.layer_types import gqa_shape
from deepspeed_tpu.parallel.mesh import initialize_topology
from deepspeed_tpu.runtime.config import MeshConfig

_VARIANTS = {
    "plain": lambda: mistral_model("tiny", max_seq_len=64),
    "qkv_bias": lambda: qwen_model("tiny", max_seq_len=64),
    "qk_norm": lambda: mistral_model("tiny", max_seq_len=64, qk_norm=True),
    "partial_rotary": lambda: phi_model("tiny", max_seq_len=64),
    "use_bias_learned_positions": lambda: opt_model("tiny", max_seq_len=64),
}


def _layer0(model, dtype):
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, model.init_params(jax.random.PRNGKey(3)))
    return jax.tree_util.tree_map(lambda a: a[0], params["layers"])


def _inputs(cfg, dtype, B=3, T_=5):
    x = jax.random.normal(jax.random.PRNGKey(7), (B, T_, cfg.hidden_size),
                          jnp.float32).astype(dtype)
    positions = jnp.arange(B * T_, dtype=jnp.int32).reshape(B, T_) % 11
    return x, positions


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "fp32"])
@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_the_pinned_q_k_v_are_attn_qkvs_bit_for_bit(variant, dtype):
    model = _VARIANTS[variant]()
    cfg = model.config
    layer = _layer0(model, dtype)
    x, positions = _inputs(cfg, dtype)
    free = jax.jit(lambda lay, x, p: T.attn_qkv(cfg, lay, x, p))(
        layer, x, positions)
    pinned = jax.jit(lambda lay, x, p: mr.attn_qkv(cfg, lay, x, p))(
        layer, x, positions)
    for a, b, heads in zip(free, pinned, (cfg.n_heads, cfg.kv_heads,
                                          cfg.kv_heads)):
        assert a.shape == b.shape == (3, 5, heads, cfg.head_dim)
        assert a.dtype == b.dtype == dtype
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
def test_by_head_is_the_product_then_the_view(bias):
    cfg = mistral_model("tiny").config
    h = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 64), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 6 * 16), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(2), (96,), jnp.bfloat16) \
        if bias else None
    got = jax.jit(lambda h, w, b: mr._by_head(cfg, h, w, 6, 16, b))(h, w, b)
    want = h @ w
    want = (want + b if bias else want).reshape(2, 3, 6, 16)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    # and the unpinned form is the same function of the same operands
    free = T.head_projection(cfg, h, w, b, 6, 16)
    assert np.array_equal(np.asarray(free, np.float32),
                          np.asarray(got, np.float32))


@pytest.mark.parametrize("kind", ["gqa_full", "gqa_window"])
def test_the_typed_layers_projection_is_what_it_was_unpinned(kind,
                                                            monkeypatch):
    model = mimo_v2_model("tiny", max_seq_len=64)
    cfg = model.config
    sh = gqa_shape(cfg, kind)
    params = model.init_params(jax.random.PRNGKey(5))
    run = next(r for r in params["layers"]
               if r[0]["attn"]["wq"].shape[-1] == sh.heads * sh.k_dim
               and r[0]["attn"]["wk"].shape[-1] == sh.kv_heads * sh.k_dim)
    layer = jax.tree_util.tree_map(lambda a: a[0].astype(jnp.bfloat16)
                                   if jnp.issubdtype(a.dtype, jnp.floating)
                                   else a[0], run[0])
    x, positions = _inputs(cfg, jnp.bfloat16)
    pinned = jax.jit(lambda lay, x, p: mr._gqa_qkv(cfg, sh, lay, x, p))(
        layer, x, positions)
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda y: y)
    free = jax.jit(lambda lay, x, p: mr._gqa_qkv(cfg, sh, lay, x, p))(
        layer, x, positions)
    for a, b in zip(free, pinned):
        assert a.shape == b.shape
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def _barriers(jaxpr) -> int:
    return str(jaxpr).count("optimization_barrier")


@pytest.mark.parametrize("family", ["mistral", "opt"])
def test_a_train_step_holds_no_barrier(family):
    model = {"mistral": mistral_model, "opt": opt_model}[family]("tiny")
    topo = initialize_topology(MeshConfig(data=1), devices=jax.devices()[:1])
    engine, *_ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1, "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "mesh": {"data": 1}, "seed": 0, "steps_per_print": 10 ** 9})
    batch = jax.ShapeDtypeStruct((1, 2, 32), jnp.int32)
    with engine.topology.mesh:
        jaxpr = jax.make_jaxpr(engine._train_batch)(
            engine.state, batch, jax.random.PRNGKey(0))
    engine.close()
    assert "dot_general" in str(jaxpr)
    assert _barriers(jaxpr) == 0


@pytest.mark.parametrize("program", ["decode", "chunk"])
@pytest.mark.parametrize("name", ["mistral", "mimo_v2"])
def test_a_serving_program_holds_one_barrier_a_pinned_product(name, program,
                                                              monkeypatch):
    pinned = []
    projection = T.head_projection

    def counted(*a, **kw):
        pinned.append(kw.get("pinned", False))
        return projection(*a, **kw)

    monkeypatch.setattr(T, "head_projection", counted)
    model = (mistral_model if name == "mistral" else mimo_v2_model)(
        "tiny", max_seq_len=256)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=16, prefill_chunk=16,
        max_seqs=4, num_pages=80), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    if program == "decode":
        jaxpr = jax.make_jaxpr(eng._decode.apart())(
            eng.params, eng._pools, S((4,), i32), S((4,), i32),
            S((4, 16), i32), S((4,), jnp.bool_), S((4,), jnp.float32),
            S((4,), i32), S((2,), jnp.uint32))
    else:
        slot = (S((), i32),) if eng._state else ()  # a window layer's rings
        jaxpr = jax.make_jaxpr(eng._prefill_chunk.apart())(
            eng.params, eng._pools, S((16,), i32), S((2,), i32),
            S((4,), i32), S((), i32), S((), i32), *slot)
    # q, k and v of every layer body traced (a scanned run's body once)
    assert pinned and all(pinned) and len(pinned) % 3 == 0
    assert _barriers(jaxpr) == len(pinned)
