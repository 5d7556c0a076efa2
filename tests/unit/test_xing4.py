"""Xing4.0 on the serving path: a residual of four streams mixed by manifold-
constrained hyper-connections (mHC) round every mixer and every feed-forward
part, over latent attention and a sigmoid-routed expert layer held whole
behind a dense first layer.

Oracles: ``benchmark/reference/mhc_mla_moe_lm.py`` (plain float32, the
residual as ``[S, n, C]``, the mixing by its equations, no cache, no code
shared with the program) for the engine's programs — logits and cached rows;
a NumPy transcription of the mixing's equations for the reference itself; the
parent commit's lowered programs (their hashes, locations stripped) for the
one-stream models, which must compile to what they compiled to.
"""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import xing4 as family  # noqa: E402
from benchmark.reference import mhc_mla_moe_lm as ref_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import model_runner  # noqa: E402
from deepspeed_tpu.models import (mistral4_model, mistral_model,  # noqa: E402
                                  xing4_config, xing4_model)
from deepspeed_tpu.models.layer_types import (latent_width,  # noqa: E402
                                              page_leaves)
from deepspeed_tpu.models.transformer import TransformerConfig  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "xing4-29b-a4b-pp7-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
CHUNK, PS, MP = (ENGINE["prefill_chunk"], ENGINE["page_size"],
                 ENGINE["max_pages_per_seq"])
N, C = DESC["hc_mult"], DESC["hidden_size"]
#: float32 on both sides, the same weights: what separates them is the order
#: of float32 sums (the program normalises after the projection, sums a
#: Sinkhorn row entry by entry and attends a chunk at a time) carried through
#: 4 layers of 40 normalisations each — 1e-5 of the largest logit at most over
#: the seeds tried, where the smallest planted fault moves them by 5e-3
TOL = 1e-4


def _engine(seed=0, **over):
    model = family.build(TINY, TINY["num_hidden_layers"], PS * MP,
                         jnp.float32)
    return InferenceEngineV2(model, RaggedInferenceConfig(**dict(ENGINE,
                                                                 **over)),
                             seed=seed)


@pytest.fixture(scope="module")
def eng():
    return _engine()


def _chunk_logits(eng, prompt, pages):
    table = np.full((MP,), eng.block.trash_page, np.int32)
    table[:len(pages)] = pages
    logits = None
    for start in range(0, len(prompt), CHUNK):
        n = min(CHUNK, len(prompt) - start)
        ids = np.zeros((CHUNK,), np.int32)
        ids[:n] = prompt[start:start + n]
        rows = np.full((CHUNK // PS,), eng.block.trash_page, np.int32)
        npg = -(-n // PS)
        rows[:npg] = pages[start // PS:start // PS + npg]
        logits, eng._pools = eng._prefill_chunk(
            eng.params, eng._pools, jnp.asarray(ids), jnp.asarray(rows),
            jnp.asarray(table), jnp.int32(start), jnp.int32(n))
    return np.asarray(logits), table


def _decode_logits(eng, table, slot, token, position):
    B = eng.block.max_seqs
    last = np.zeros((B,), np.int32)
    pos = np.zeros((B,), np.int32)
    act = np.zeros((B,), bool)
    tab = np.full((B, MP), eng.block.trash_page, np.int32)
    last[slot], pos[slot], act[slot], tab[slot] = token, position, True, table
    if not hasattr(eng, "_logits_program"):
        eng._logits_program = jax.jit(
            lambda p, pools, *a: model_runner.paged_decode(eng.cfg, p, pools,
                                                           *a))
    logits, eng._pools = eng._logits_program(
        eng.params, eng._pools, jnp.asarray(last), jnp.asarray(pos),
        jnp.asarray(tab), jnp.asarray(act))
    return np.asarray(logits[slot])


def _program_logits(eng, prompt, steps):
    """Chunked prefill, then ``steps`` decode steps through the latent pages,
    each fed the program's own arg-max -> (logits ``[steps + 1, V]``, the
    tokens fed)."""
    pages = list(range(3, 3 + -(-(len(prompt) + steps) // PS)))
    logits, table = _chunk_logits(eng, prompt, pages)
    out, toks = [logits], []
    for k in range(steps):
        toks.append(int(np.argmax(out[-1])))
        out.append(_decode_logits(eng, table, 1, toks[-1], len(prompt) + k))
    return np.stack(out), toks


# ------------------------------------------------------------ the description
def test_the_stack_is_a_dense_prologue_and_expert_layers_with_mixing():
    cfg = xing4_model("tiny").config
    assert cfg.hc_mult == 4 and cfg.layer_runs == ((("mla",), 1),
                                                   (("mla",), 3))
    params = xing4_model("tiny").init_params(jax.random.PRNGKey(0))
    dense, experts = (run[0] for run in params["layers"])
    assert "router" not in dense["mlp"] and dense["mlp"]["w_up"].shape == (
        1, 64, 128)
    assert experts["mlp"]["w_up"].shape == (3, 8, 64, 32)  # all 8 held
    for tree, n in ((dense, 1), (experts, 3)):
        for part in ("mixer", "ffn"):
            hc = tree["hc"][part]
            assert hc["phi"].shape == (n, 4 * 64, 2 * 4 + 16)
            assert hc["alpha"].shape == (n, 3) and hc["b"].shape == (n, 24)
    # the dynamic terms decide: a unit-RMS input gives phi's projection unit
    # variance, so sigmoid's argument moves by ~1 from token to token
    phi = np.asarray(experts["hc"]["mixer"]["phi"], np.float32)
    assert 0.7 < phi.std() * np.sqrt(4 * 64) < 1.3
    # a one-stream model has no such parameter
    assert "hc" not in mistral4_model("tiny").init_params(
        jax.random.PRNGKey(0))["layers"][0]
    # the published widths: 512 + 64 values a token, 640 lanes as laid out
    full = xing4_config("29b", n_layers=6, dense_layers=1)
    assert page_leaves(full) == {"latent": (6, 640)}
    assert latent_width(full) == 640 and full.kv_lora_rank + \
        full.qk_rope_head_dim == 576
    assert full.moe_held_count == full.moe_experts == 64


def test_training_and_unread_mixers_refuse_by_name():
    model = xing4_model("tiny")
    with pytest.raises(NotImplementedError, match="xing4 is served only"):
        model.loss_fn(None, None, None)
    from deepspeed_tpu.models.transformer import transformer_forward

    cfg = TransformerConfig(vocab_size=64, hidden_size=32, n_layers=2,
                            n_heads=2, hc_mult=2)
    with pytest.raises(NotImplementedError, match="hc_mult=2 streams"):
        transformer_forward(cfg, None, jnp.zeros((1, 4), jnp.int32))
    # a mixer whose layer function reads the residual as it is
    kda = TransformerConfig(vocab_size=64, hidden_size=32, n_layers=2,
                            n_heads=2, hc_mult=2, layer_period=("kda",),
                            kda_heads=2, kda_head_dim=16)
    with pytest.raises(NotImplementedError, match=r"\['kda'\] layer functions"):
        model_runner._scan_layers(kda, {"layers": ({},)}, {}, None, {})


# ------------------------------------------------- (i) program vs reference
@pytest.mark.parametrize("n_prompt", [5, 8, 21, 37])
def test_chunks_then_decode_agree_with_the_references_full_pass(eng, n_prompt):
    """Inside a chunk, a whole chunk, across two and across four chunk
    boundaries (and past the tiny original context of 16): logits, not
    tokens."""
    rng = np.random.RandomState(n_prompt)
    prompt = rng.randint(0, DESC["vocab_size"], n_prompt).tolist()
    mine, toks = _program_logits(eng, prompt, 4)
    ref, rows = ref_lm.forward(DESC, eng.params, prompt + toks,
                               logits_from=n_prompt - 1)
    ref = np.asarray(ref)
    assert np.abs(mine - ref).max() <= TOL * np.abs(ref).max()
    # what the layers cached, chunk program and decode program alike
    kept = np.asarray(eng._pools["latent"])[:, 3:3 + -(-(n_prompt + 4) // PS)]
    kept = kept.reshape(kept.shape[0], -1, kept.shape[-1])
    width = DESC["kv_lora_rank"] + DESC["qk_rope_head_dim"]
    for got, want in zip(kept, rows):
        want = np.asarray(want)
        np.testing.assert_allclose(got[:len(want), :width], want, atol=2e-5)
        assert not got[:, width:].any()    # the lane padding enters nothing


def test_requests_through_put_and_step_have_no_regret(eng):
    prompts = [np.random.RandomState(s).randint(0, 256, n).tolist()
               for s, n in ((1, 6), (2, 21), (3, 50))]
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=5))
            for p in prompts]
    got = {u: [] for u in uids}
    while eng.has_work():
        for u, o in eng.step().items():
            got[u] += o["tokens"]
    for u, prompt in zip(uids, prompts):
        toks = got[u]
        ref, _ = ref_lm.forward(DESC, eng.params, prompt + toks[:-1],
                                logits_from=len(prompt) - 1)
        for row, t in zip(np.asarray(ref), toks):
            assert float(row.max() - row[t]) <= TOL * float(np.abs(row).max())
    eng.assert_no_leaks()


# ------------------------------------------- (ii) the reference's mixing alone
def _numpy_mixing(x, phi, alpha, b, n, eps, rounds, hc_eps, clamp):
    """Equations 1 - 5's coefficients, token by token, in float64."""
    pres, posts, ress = [], [], []
    for tok in np.asarray(x, np.float64):
        flat = tok.reshape(-1)
        u = flat / np.sqrt(np.mean(flat ** 2) + eps)
        m = u @ phi
        sig = lambda z: 1.0 / (1.0 + np.exp(-z))  # noqa: E731
        pres.append(sig(alpha[0] * m[:n] + b[:n]))
        posts.append(2.0 * sig(alpha[1] * m[n:2 * n] + b[n:2 * n]))
        mat = np.exp(np.clip(alpha[2] * m[2 * n:].reshape(n, n)
                             + b[2 * n:].reshape(n, n), -clamp, clamp))
        for _ in range(rounds):
            mat = mat / (mat.sum(axis=1, keepdims=True) + hc_eps)
            mat = mat / (mat.sum(axis=0, keepdims=True) + hc_eps)
        ress.append(mat)
    return np.stack(pres), np.stack(posts), np.stack(ress)


@pytest.fixture(scope="module")
def sublayer():
    rng = np.random.RandomState(7)
    w = {"phi": rng.normal(size=(N * C, 2 * N + N * N)) / np.sqrt(N * C),
         "alpha": np.asarray([1.0, 0.7, 1.3]),
         "b": rng.normal(size=(2 * N + N * N,)) * 0.5}
    x = rng.normal(size=(9, N, C)) * rng.uniform(0.3, 3.0, size=(9, 1, 1))
    return x, w


def test_the_references_mixing_is_the_equations(sublayer):
    x, w = sublayer
    wj = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    got = ref_lm.mixing(DESC, jnp.asarray(x, jnp.float32), wj)
    want = _numpy_mixing(x, w["phi"], w["alpha"], w["b"], N,
                         DESC["norm_eps"], DESC["hc_sinkhorn_iters"],
                         DESC["hc_eps"], DESC["hc_clamp"])
    for g, v in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), v, rtol=2e-5, atol=2e-6)
    h_pre, h_post, h_res = want
    y = np.random.RandomState(8).normal(size=(9, C))
    np.testing.assert_allclose(
        np.asarray(ref_lm.read_in(got[0], jnp.asarray(x, jnp.float32))),
        np.einsum("sn,snc->sc", h_pre, x), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(ref_lm.write_back(got[1], got[2],
                                     jnp.asarray(x, jnp.float32),
                                     jnp.asarray(y, jnp.float32))),
        np.einsum("sij,sjc->sic", h_res, x)
        + h_post[:, :, None] * y[:, None, :], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mhc,doubly", [("full", True), ("one_round", False)])
def test_h_res_is_doubly_stochastic_after_20_rounds_and_not_after_1(
        sublayer, mhc, doubly):
    x, w = sublayer
    wj = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    h_res = np.asarray(ref_lm.mixing(DESC, jnp.asarray(x, jnp.float32), wj,
                                     mhc)[2])
    off = max(np.abs(h_res.sum(axis=-1) - 1).max(),
              np.abs(h_res.sum(axis=-2) - 1).max())
    assert (off < 1e-5) == doubly, off
    assert (h_res > 0).all()


@pytest.mark.parametrize("value", [100.0, -100.0])
def test_the_clamp_keeps_h_res_finite(value):
    """(v) ``H~`` of +-100: ``exp`` of it unclamped is inf (or 0 over 0)."""
    h = np.full((2, N, N), -value, np.float32)
    h[:, 0, 0] = value
    assert not np.isfinite(np.asarray(ref_lm.sinkhorn(
        jnp.asarray(h), 20, 1e-6))).all() or value < 0
    w = {"phi": jnp.zeros((N * C, 2 * N + N * N), jnp.float32),
         "alpha": jnp.ones((3,), jnp.float32),
         "b": jnp.concatenate([jnp.zeros((2 * N,), jnp.float32),
                               jnp.asarray(h[0].reshape(-1))])}
    h_res = np.asarray(ref_lm.mixing(DESC, jnp.ones((2, N, C), jnp.float32),
                                     w)[2])
    assert np.isfinite(h_res).all()
    np.testing.assert_allclose(h_res.sum(axis=-1), 1.0, atol=1e-4)
    # the program's: the same matrix through its entry-by-entry rounds
    rows = [[jnp.exp(jnp.clip(jnp.asarray(h[:, i, j]), -30.0, 30.0))
             for j in range(N)] for i in range(N)]
    mine = np.asarray(model_runner._sinkhorn(rows, 20, 1e-6))
    assert np.isfinite(mine).all()
    np.testing.assert_allclose(np.moveaxis(mine, -1, 0), h_res, rtol=1e-5,
                               atol=1e-7)


def test_the_programs_pair_is_the_references_mixing(sublayer):
    """``_stream_read`` / ``_stream_write`` on the streams side by side
    against the reference on ``[S, n, C]``."""
    x, w = sublayer
    cfg = xing4_model("tiny").config
    xj = jnp.asarray(x, jnp.float32)
    layer = {"hc": {"ffn": {k: jnp.asarray(v, jnp.float32)
                            for k, v in w.items()}}}
    h, mix = model_runner._stream_read(cfg, layer, xj.reshape(1, 9, N * C),
                                       "ffn")
    h_pre, h_post, h_res = ref_lm.mixing(DESC, xj, layer["hc"]["ffn"])
    np.testing.assert_allclose(np.asarray(h[0]),
                               np.asarray(ref_lm.read_in(h_pre, xj)),
                               rtol=2e-5, atol=2e-5)
    y = jnp.asarray(np.random.RandomState(9).normal(size=(1, 9, C)),
                    jnp.float32)
    out = model_runner._stream_write(xj.reshape(1, 9, N * C), y, mix)
    np.testing.assert_allclose(
        np.asarray(out[0]).reshape(9, N, C),
        np.asarray(ref_lm.write_back(h_post, h_res, xj, y[0])),
        rtol=2e-5, atol=2e-5)
    # entry by copy, exit by sum
    e = jnp.asarray(np.random.RandomState(1).normal(size=(2, 3, C)),
                    jnp.float32)
    x0 = model_runner._streams_in(cfg, e)
    assert x0.shape == (2, 3, N * C)
    np.testing.assert_array_equal(np.asarray(x0).reshape(2, 3, N, C),
                                  np.broadcast_to(np.asarray(e)[:, :, None],
                                                  (2, 3, N, C)))
    np.testing.assert_allclose(np.asarray(model_runner._streams_out(cfg, x0)),
                               N * np.asarray(e), rtol=1e-6)


@pytest.mark.parametrize("family", ["llama", "mimo_v2"])
def test_the_other_read_mixers_carry_streams_the_same_in_every_program(
        family):
    """``attn`` and the grouped-query types by layer type go through the same
    pair: with two streams, a prompt prefilled in chunks of 8, in one chunk
    and (``attn``) whole, then decoded, yields the same tokens."""
    from deepspeed_tpu.models import llama_model, mimo_v2_model

    build = {"llama": llama_model, "mimo_v2": mimo_v2_model}[family]
    prompt = np.random.RandomState(4).randint(0, 256, 27).tolist()

    def tokens(streams, chunk):
        model = build("tiny", max_seq_len=128, hc_mult=streams)
        eng = InferenceEngineV2(model, RaggedInferenceConfig(
            dtype="fp32", page_size=4, num_pages=64, max_seqs=2,
            max_pages_per_seq=32, prefill_chunk=chunk), seed=3)
        if streams > 1:
            assert "hc" in jax.tree_util.tree_leaves(
                eng.params["layers"], is_leaf=lambda t: isinstance(
                    t, dict) and "hc" in t)[0]
        return eng.generate_all([RaggedRequest(prompt_ids=prompt,
                                               max_new_tokens=6)])[0]

    chunks = (8, 32) + ((0,) if family == "llama" else ())
    got = [tokens(2, c) for c in chunks]
    assert all(g == got[0] for g in got), got


# ------------------------------------------ (iii) the controls move the logits
@pytest.mark.parametrize("control", [
    {"mhc": "static"}, {"mhc": "one_round"}, {"mhc": "post_unscaled"},
    {"router": "softmax"}, {"weights_dtype": jnp.float8_e4m3fn}],
    ids=["mhc_static", "mhc_one_round", "mhc_post_unscaled", "router_softmax",
         "float8_weights"])
def test_each_control_moves_the_logits_by_more_than_the_tolerance(eng,
                                                                  control):
    prompt = np.random.RandomState(11).randint(0, 256, 37).tolist()
    clean, _ = ref_lm.forward(DESC, eng.params, prompt, logits_from=30)
    off, _ = ref_lm.forward(DESC, eng.params, prompt, logits_from=30,
                            **control)
    clean, off = np.asarray(clean), np.asarray(off)
    assert np.abs(off - clean).max() > 10 * TOL * np.abs(clean).max()


# ------------------------------------- the latent decode kernel at rank 512 + 64
@pytest.mark.parametrize("lengths,active", [
    ([45, 0, 16, 130], [True, False, True, True])])
def test_the_decode_kernel_at_the_published_rank_over_a_640_lane_pool(
        lengths, active):
    """``dstpu_mla_decode`` interpreted at Xing4.0's row: 512 + 64 values in
    640 lanes (five tiles, where Mistral-Small-4's 256 + 64 take three).  NaN
    in the 64 lanes of padding, in the pages past a row's length, in an
    inactive row's pages and in the trash page: none may reach an output."""
    from deepspeed_tpu.ops.pallas.mla_attention import mla_decode_attention

    rank, dr, ps, mp, L, NH = 512, 64, 16, 9, 2, 4
    B = len(lengths)
    P = B * mp
    rng = np.random.default_rng(58)
    pool = rng.normal(size=(L, P + 1, ps, 640)).astype(np.float32)
    pool[..., rank + dr:] = np.nan
    pool[:, P] = np.nan
    q = (rng.normal(size=(B, NH, rank + dr)) / 24).astype(np.float32)
    table = np.arange(P, dtype=np.int32).reshape(B, mp)
    for b, n in enumerate(lengths):
        live = -(-n // ps) if active[b] else 0
        pool[:, table[b, live:]] = np.nan
    pos = np.maximum(np.asarray(lengths, np.int32) - 1, 0)
    out = np.asarray(mla_decode_attention(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(table),
        jnp.asarray(pos), 1, jnp.asarray(active), rank=rank))
    for b, n in enumerate(lengths):
        if not active[b]:
            assert not out[b].any()
            continue
        rows = pool[1, table[b]].reshape(-1, 640)[:n]
        sc = q[b] @ rows[:, :rank + dr].T
        pr = np.exp(sc - sc.max(-1, keepdims=True))
        want = (pr / pr.sum(-1, keepdims=True)) @ rows[:, :rank]
        np.testing.assert_allclose(out[b], want, rtol=0, atol=2e-5)


# ------------------------------------------------- (iv) one-stream models
def _lowered_hash(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).as_text()
    text = re.sub(r"loc\(.*?\)\n?|#loc.*\n", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _one_stream_programs(model, chunk):
    """(decode, chunk) of a tiny one-stream model, lowered over abstract
    weights and pools as the engine would hand them."""
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=4, num_pages=32, max_seqs=2,
        max_pages_per_seq=8, prefill_chunk=chunk))
    cfg, B, mp = eng.cfg, 2, 8
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    decode = _lowered_hash(
        lambda p, pools, *a: model_runner.paged_decode(cfg, p, pools, *a),
        eng.params, eng._pools, i32(B), i32(B), i32(B, mp),
        jnp.zeros((B,), bool))
    chunked = _lowered_hash(
        lambda p, pools, *a: model_runner.paged_prefill_chunk(
            cfg, p, pools, *a),
        eng.params, eng._pools, i32(chunk), i32(chunk // 4), i32(mp),
        jnp.int32(0), jnp.int32(3))
    return decode, chunked


#: the lowered text of the parent commit's programs (c59049c, before any
#: stream was carried), locations stripped: this file's ``_lowered_hash``
#: run in a checkout of that commit
#: (PR 61 pinned every paged program's head projections — ``h @ wq``
#: behind an optimization barrier, ``transformer.head_projection`` — a
#: change these programs were meant to take: the hashes of the programs
#: that hold one are its tree's, jax 0.9.0.)
PARENT_PROGRAMS = {
    "mistral": ("cc62c9d28da856c0", "b0a8be65dccd46cd"),
    "mistral4": ("99a7e4c4266c9a8b", "855b7928d9e3cd10"),
}


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_one_stream_models_lower_to_the_parents_programs(name):
    model = {"mistral": mistral_model,
             "mistral4": mistral4_model}[name]("tiny", max_seq_len=32)
    assert model.config.hc_mult == 1
    assert _one_stream_programs(model, 8) == PARENT_PROGRAMS[name]


def test_with_one_stream_the_pair_traces_nothing():
    cfg = mistral4_model("tiny").config
    x, y = jnp.ones((1, 2, 64)), jnp.ones((1, 2, 64))
    assert model_runner._stream_read(cfg, {}, x, "mixer") == (x, None)
    assert model_runner._streams_in(cfg, x) is x
    assert model_runner._streams_out(cfg, x) is x
    jaxpr = jax.make_jaxpr(lambda a, b: model_runner._stream_write(a, b, None)
                           )(x, y)
    assert [e.primitive.name for e in jaxpr.eqns] == ["add"]
