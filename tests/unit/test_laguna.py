"""Laguna-S-2.1 on the serving path: what a grouped-query layer computes with
follows its type — a full layer 12 (48) query heads, half a head under YaRN
with the attention factor on cos and sin, pages; a window layer 18 (72) query
heads, the whole head under the plain table of another base, a ring of 16
(512) — both over 2 (8) K/V heads, every head's output through a learned gate,
behind a dense first layer, over a softmax-routed expert share with a routed
scale and a shared expert.

Oracles: ``benchmark/reference/gated_swa_moe_lm.py`` (plain float32, every
layer at every position, no cache, no ring, no code shared with the program)
for the engine's programs — logits and cached rows; ``transformers``'
``_compute_yarn_parameters`` and a softmax top-k written here for the
reference itself; a loop over positions written from the equations for its
attention.  The tiny groups of queries a K/V head are the published 6 and 9,
the tiny window (16) is smaller than the tiny chunk (32) and larger than the
tiny page (8), half of the tiny head (16) is rotated on a full layer.
"""

import hashlib
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.families import laguna as family  # noqa: E402
from benchmark.reference import gated_swa_moe_lm as ref_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import model_runner  # noqa: E402
from deepspeed_tpu.models import (laguna_config, laguna_model,  # noqa: E402
                                  mimo_v2_model)
from deepspeed_tpu.models.laguna import laguna_runs  # noqa: E402
from deepspeed_tpu.models.layer_types import (gqa_shape,  # noqa: E402
                                              layer_type, page_leaves,
                                              served_runs, state_leaves)
from deepspeed_tpu.models.transformer import mlp_block  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import merged_keys  # noqa: E402
from deepspeed_tpu.telemetry.spans import get_span_recorder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "laguna-s-2.1-ep8-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
CHUNK, PS, MP = (ENGINE["prefill_chunk"], ENGINE["page_size"],
                 ENGINE["max_pages_per_seq"])
WINDOW = DESC["sliding_window"]
LAYERS = TINY["num_hidden_layers"]


def _engine(seed=0, **over):
    model = family.build(TINY, LAYERS, PS * MP, jnp.float32)
    return InferenceEngineV2(model, RaggedInferenceConfig(**dict(ENGINE,
                                                                 **over)),
                             seed=seed)


def _chunk_logits(eng, prompt, pages, slot):
    """The chunk program called as the engine calls it, chunk by chunk, on
    pages and a slot taken by hand -> the logits of the prompt's last token."""
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
            jnp.asarray(table), jnp.int32(start), jnp.int32(n),
            jnp.int32(slot))
    return np.asarray(logits), table


def _decode_logits(eng, table, slot, token, position):
    """One step of ``paged_decode`` for one row -> its logits."""
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


def _serve(eng, prompts, new=8):
    uids = [eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=new))
            for p in prompts]
    got = {u: [] for u in uids}
    steps = []
    while eng.has_work():
        out = eng.step()
        steps.append(dict(eng._step_counts))
        for u, o in out.items():
            got[u] += o["tokens"]
    return [got[u] for u in uids], steps


def _regrets(eng, prompt, toks):
    ref, _ = ref_lm.forward(DESC, eng.params, prompt + toks[:-1],
                            logits_from=len(prompt) - 1)
    return [float(row.max() - row[t]) / float(np.abs(row).max())
            for row, t in zip(np.asarray(ref), toks)]


# ------------------------------------------------------------ the description
def test_what_a_layer_computes_with_follows_its_type():
    cfg = laguna_model("tiny").config
    full, win = gqa_shape(cfg, "gqa_full"), gqa_shape(cfg, "gqa_window")
    assert (full.heads, full.kv_heads, full.k_dim, full.v_dim, full.rot,
            full.split, full.window, full.sink) == (12, 2, 16, 16, 8, 8, 0,
                                                    False)
    assert (win.heads, win.kv_heads, win.k_dim, win.v_dim, win.rot,
            win.split, win.window, win.sink) == (18, 2, 16, 16, 16, 0, 16,
                                                 False)
    assert (full.theta, win.theta) == (5e5, 1e4)
    assert full.yarn[:4] == (128.0, 32, 32.0, 1.0) and win.yarn is None
    assert full.yarn[4] == pytest.approx(0.1 * math.log(128) + 1)
    # the published widths: 4,096 B a token a full layer on 3 of 9 layers,
    # a ring of 512 rows x 2,048 values = 2 MiB a slot on the other 6
    big = laguna_config("118b", n_layers=9, moe_held_count=32)
    assert page_leaves(big) == {"k": (3, 1024), "v": (3, 1024)}
    assert state_leaves(big) == {"win_k": (6, (512, 1024), None),
                                 "win_v": (6, (512, 1024), None)}
    bfull, bwin = gqa_shape(big, "gqa_full"), gqa_shape(big, "gqa_window")
    assert (bfull.heads, bfull.rot, bfull.split) == (48, 64, 64)
    assert (bwin.heads, bwin.rot, bwin.split) == (72, 128, 0)
    shapes = jax.eval_shape(laguna_model(config=big).init_params,
                            jax.random.PRNGKey(0))["layers"]
    (first,), period = shapes
    assert first["attn"]["wq"].shape == (1, 3072, 48 * 128)
    assert first["attn"]["wg"].shape == (1, 3072, 48)
    assert first["mlp"]["w_gate"].shape == (1, 3072, 12288)
    assert [t["attn"]["wq"].shape[-1] // 128 for t in period] == [72, 72, 72,
                                                                  48]
    assert [t["attn"]["wg"].shape for t in period] == [
        (2, 3072, 72)] * 3 + [(2, 3072, 48)]
    assert [t["attn"]["wo"].shape for t in period] == [
        (2, 72 * 128, 3072)] * 3 + [(2, 48 * 128, 3072)]
    assert period[0]["mlp"]["w_gate"].shape == (2, 32, 3072, 1024)
    assert period[0]["mlp"]["shared_w_gate"].shape == (2, 3072, 1024)
    assert period[0]["mlp"]["router"].shape == (2, 3072, 256)
    assert "shared_gate" not in period[0]["mlp"]
    assert not any("sink" in t["attn"] for t in period)
    # and the engine's pools at the tiny sizes
    eng = _engine()
    P, S = ENGINE["num_pages"] + 1, ENGINE["max_seqs"] + 1
    assert {n: a.shape for n, a in eng._pools.items()} == {
        "k": (3, P, PS, 32), "v": (3, P, PS, 32),
        "win_k": (6, S, WINDOW, 32), "win_v": (6, S, WINDOW, 32),
        "moe_stats": (5,)}


@pytest.mark.parametrize("layers,periods", [(1, 0), (5, 1), (9, 2),
                                            (48, None)])
def test_the_published_pattern_as_runs(layers, periods):
    got = laguna_runs(layers)
    kinds = [k for p, n in got for _ in range(n) for k in p]
    assert len(kinds) == layers and got[0] == (("gqa_full",), 1)
    if layers == 48:
        assert [("sliding_attention" if k == "gqa_window" else
                 "full_attention") for k in kinds] == CONFIG["layer_types"]
        heads = {"gqa_full": 48, "gqa_window": 72}
        assert [heads[k] for k in kinds] == \
            CONFIG["num_attention_heads_per_layer"]
        assert got[-1] == (("gqa_window",) * 3, 1)  # the list's last three
    else:
        assert got[1:] == (((("gqa_window",) * 3 + ("gqa_full",), periods),)
                           if periods else ())


@pytest.mark.parametrize("layers", [0, 2, 4, 7, 10, 47])
def test_a_depth_that_is_not_layer_0_and_whole_periods_is_refused(layers):
    with pytest.raises(ValueError, match="whole periods"):
        laguna_runs(layers)


def test_training_is_refused_by_what_is_missing():
    model = laguna_model("tiny")
    with pytest.raises(NotImplementedError, match="window in the flash"):
        model.loss_fn(None, None)
    assert [tuple(t.name for t in types_) for types_, _ in
            served_runs(model.config)] == [
        ("gqa_full",), ("gqa_window",) * 3 + ("gqa_full",)]


# -------------------------------------------- the programs against the reference
@pytest.mark.parametrize("n,steps,kernels", [
    (10, 40, "xla"), (24, 6, "xla"), (70, 6, "xla"),
    (10, 40, "interpreted"), (24, 6, "interpreted"), (70, 6, "interpreted")],
    ids=["shorter_than_the_window_then_two_wraps-xla",
         "across_the_window_inside_a_chunk-xla",
         "across_two_chunk_boundaries-xla",
         "shorter_than_the_window_then_two_wraps-interpreted",
         "across_the_window_inside_a_chunk-interpreted",
         "across_two_chunk_boundaries-interpreted"])
def test_chunked_prefill_then_decode_matches_the_reference_logits(
        n, steps, kernels, monkeypatch):
    """The last chunk's logits and the decode steps' against the reference's
    full forward — logits, not tokens — and then the pool's and the ring's
    rows against the reference's keys and values at the same positions (the
    ring: the last 16)."""
    if kernels == "interpreted":
        monkeypatch.setenv("DSTPU_PAGED_KERNEL", "1")
    rng = np.random.default_rng(n)
    eng = _engine()
    slot = 2
    prompt = rng.integers(0, 256, n).tolist()
    pages = list(range(7, 7 + MP // 2))
    got, table = _chunk_logits(eng, prompt, pages, slot)
    rows, toks = [got], list(prompt)
    for _ in range(steps):
        toks.append(int(np.argmax(rows[-1])))
        rows.append(_decode_logits(eng, table, slot, toks[-1], len(toks) - 1))
    ref, kv = ref_lm.forward(DESC, eng.params, toks, logits_from=n - 1)
    np.testing.assert_allclose(np.stack(rows), ref, rtol=0, atol=1e-4)
    S, used = len(toks), -(-len(toks) // PS)
    seen = {"full": 0, "window": 0}
    for (k, v), windowed in zip(kv, DESC["window_layers"]):
        sh = gqa_shape(eng.cfg, "gqa_window" if windowed else "gqa_full")
        if windowed:
            l, at = seen["window"], np.arange(max(S - WINDOW, 0), S)
            seen["window"] += 1
            kept_k = np.asarray(eng._pools["win_k"][l, slot])[at % WINDOW]
            kept_v = np.asarray(eng._pools["win_v"][l, slot])[at % WINDOW]
        else:
            l, at = seen["full"], np.arange(S)
            seen["full"] += 1
            kept_k, kept_v = (np.asarray(
                eng._pools[nm][l, np.asarray(pages[:used])]).reshape(
                    used * PS, -1)[:S] for nm in ("k", "v"))
        np.testing.assert_allclose(
            merged_keys(kept_k, sh.split, sh.kv_heads), np.asarray(k)[at],
            rtol=0, atol=1e-5)
        np.testing.assert_allclose(
            kept_v.reshape(len(at), sh.kv_heads, sh.v_dim), np.asarray(v)[at],
            rtol=0, atol=1e-5)


def test_put_step_serves_it_and_the_step_records_count_by_type():
    """Three sequences interleaved in different slots, prefilling and decoding
    in the same steps, each against the reference alone; the rings wrap more
    than twice (60 decoded tokens over a window of 16); and what the steps'
    records and spans carry."""
    eng = _engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (70, 13, 100)]
    get_span_recorder().clear()
    toks, steps = _serve(eng, prompts, new=60)
    for p, t in zip(prompts, toks):
        assert len(t) == 60 and max(_regrets(eng, p, t)) < 1e-5
    eng.assert_no_leaks()
    assert eng.state_slots.in_use == 0
    dec = [s for s in steps if s["decode_rows"] == 3]
    assert len(dec) > 2 * WINDOW and all(
        s["window_kv_tokens"] == WINDOW * 3 < s["full_kv_tokens"]
        for s in dec)
    assert all(b["full_kv_tokens"] == a["full_kv_tokens"] + 3
               for a, b in zip(dec, dec[1:]))
    alone = next(s for s in steps if s["decode_rows"] == 1)
    assert alone["full_kv_tokens"] == alone["window_kv_tokens"] == 13 + 1
    for key in ("moe_local_picks", "moe_experts_touched", "moe_padded_rows",
                "moe_layer_calls", "long_rows"):
        assert key in dec[0], key
    spans = get_span_recorder().spans()
    step_spans = [sp.attrs for sp in spans if sp.name == "serve_step"]
    for key in ("full_kv_tokens", "window_kv_tokens", "long_rows",
                "page_tokens_in_use", "state_slots_in_use",
                "moe_local_picks", "moe_layer_calls"):
        assert any(key in a for a in step_spans), key
    held = [a["page_tokens_in_use"] for a in step_spans]
    assert PS * sum(-(-len(p) // PS) for p in prompts) <= max(held) \
        <= PS * sum(-(-(len(p) + 60) // PS) for p in prompts)
    assert max(a["state_slots_in_use"] for a in step_spans) == 3
    chunks = [sp.attrs for sp in spans if sp.name == "prefill"
              and sp.cat == "phase"]
    assert sorted(c["ctx_tokens"] for c in chunks) == [0, 0, 0, 32, 32, 64,
                                                       64, 96]


def test_read_kv_gives_the_pages_and_the_rings_in_position_order():
    eng = _engine()
    prompt = np.random.default_rng(3).integers(0, 256, 41).tolist()
    uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=30))
    toks = []
    while len(toks) < 9:
        toks += eng.step().get(uid, {"tokens": []})["tokens"]
    kept = eng.read_kv(uid)
    n = len(prompt) + len(toks) - 1
    _, kv = ref_lm.forward(DESC, eng.params, prompt + toks[:-1])
    assert [r["first"] for r in kept] == [
        n - WINDOW if w else 0 for w in DESC["window_layers"]]
    for got, (k, v) in zip(kept, kv):
        lo = got["first"]
        np.testing.assert_allclose(got["k"], np.asarray(k)[lo:n], atol=1e-5)
        np.testing.assert_allclose(got["v"], np.asarray(v)[lo:n], atol=1e-5)
    eng.abort_all("done")


# ------------------------------------------------ the reference held to others
def _layer_weights(rng, windowed, H=64):
    nh = DESC["heads_window" if windowed else "heads_full"]
    g, d = DESC["kv_heads"], DESC["head_dim"]
    w = lambda *s: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)  # noqa: E731
    return {"norm1": {"scale": jnp.ones((H,), jnp.float32)},
            "attn": {"wq": w(H, nh * d), "wk": w(H, g * d), "wv": w(H, g * d),
                     "wo": w(nh * d, H), "wg": w(H, nh) * 5}}


@pytest.mark.parametrize("case", ["tiny", "published"])
def test_the_references_yarn_table_is_transformers(case):
    """``yarn_table`` and the attention factor against ``transformers.
    modeling_rope_utils._compute_yarn_parameters`` for ``partial_rotary_factor``
    0.5, and the program's table (``GqaShape.rotate`` reads
    ``yarn_inv_freq``) against both."""
    pytest.importorskip("torch")
    rope = pytest.importorskip("transformers.modeling_rope_utils")
    from deepspeed_tpu.models.transformer import yarn_inv_freq

    cfg = TINY if case == "tiny" else CONFIG
    p = cfg["rope_parameters"]["full_attention"]
    scaling = {k: p[k] for k in ("factor", "original_max_position_embeddings",
                                 "beta_fast", "beta_slow", "rope_type")}
    hf = types.SimpleNamespace(
        rope_theta=p["rope_theta"], head_dim=cfg["head_dim"],
        partial_rotary_factor=p["partial_rotary_factor"],
        rope_scaling=scaling, hidden_size=cfg["hidden_size"],
        num_attention_heads=cfg["num_attention_heads"],
        max_position_embeddings=cfg["max_position_embeddings"])
    want, factor = rope._compute_yarn_parameters(hf, "cpu")
    rot = int(cfg["head_dim"] * p["partial_rotary_factor"])
    got, mine = ref_lm.yarn_table(
        rot, p["rope_theta"], p["factor"],
        p["original_max_position_embeddings"], p["beta_fast"], p["beta_slow"])
    assert want.shape == (rot // 2,)
    np.testing.assert_allclose(np.asarray(got), want.numpy(), rtol=2e-6)
    # the key the config carries is what the formula gives
    assert mine == pytest.approx(factor) == pytest.approx(
        p["attention_factor"])
    np.testing.assert_allclose(np.asarray(yarn_inv_freq(
        rot, p["rope_theta"], p["factor"],
        p["original_max_position_embeddings"], p["beta_fast"],
        p["beta_slow"])), want.numpy(), rtol=2e-6)
    # the blend is no plain table: some pair is slowed by the whole factor
    plain = p["rope_theta"] ** (-2.0 * np.arange(rot // 2) / rot)
    assert np.asarray(got)[-1] == pytest.approx(plain[-1] / p["factor"],
                                                rel=1e-5)
    assert np.asarray(got)[0] == pytest.approx(plain[0])


def test_the_references_router_is_a_softmax_top_k_with_the_routed_scale():
    rng = np.random.default_rng(4)
    S, H, E, K = 50, 64, 8, 2
    h = rng.normal(size=(S, H)).astype(np.float32)
    wr = rng.normal(size=(H, E)).astype(np.float32)
    z = h.astype(np.float64) @ wr
    s = np.exp(z - z.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    want = np.zeros((S, E))
    for t in range(S):
        top = np.argsort(-s[t])[:K]
        want[t, top] = s[t, top] / s[t, top].sum() * 2.5
    whole = dict(DESC, experts_first=0, experts_held=E)
    assert (DESC["routed_scale"], DESC["num_experts_per_tok"]) == (2.5, K)
    with jax.default_matmul_precision("highest"):
        got = ref_lm.route(whole, jnp.asarray(h), jnp.asarray(wr))
        part = ref_lm.route(DESC, jnp.asarray(h), jnp.asarray(wr))
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
    first = DESC["experts_first"]
    np.testing.assert_allclose(
        np.asarray(part), want[:, first:first + DESC["experts_held"]],
        rtol=0, atol=1e-5)


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
def test_the_references_layers_are_their_equations_token_by_token(windowed):
    """Each attention type of the reference against a loop over positions
    written from the equations alone: a full layer rotates the first 8 of 16
    lanes by the YaRN table with cos and sin times the factor, a window layer
    all 16 by the plain table; the type's query heads; a gate a head."""
    rng = np.random.default_rng(9)
    S, H = 37, 64
    nh = DESC["heads_window" if windowed else "heads_full"]
    g, d = DESC["kv_heads"], DESC["head_dim"]
    x = rng.normal(size=(S, H)).astype(np.float32)
    w = _layer_weights(rng, windowed)
    a = {n: np.asarray(m, np.float64) for n, m in w["attn"].items()}
    h = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                    + DESC["norm_eps"])
    if windowed:
        half, mult = d // 2, 1.0
        freq = 1e4 ** (-2.0 * np.arange(half) / d)
    else:
        y = DESC["yarn"]
        half, mult = DESC["rot_full"] // 2, 0.1 * math.log(y["factor"]) + 1
        table, _ = ref_lm.yarn_table(
            DESC["rot_full"], DESC["rope_theta"], y["factor"],
            y["original_max_position_embeddings"], y["beta_fast"],
            y["beta_slow"])
        freq = np.asarray(table, np.float64)

    def rot(z, t):
        z = z.copy()
        for i in range(half):
            c, s_ = mult * math.cos(t * freq[i]), mult * math.sin(t * freq[i])
            a0, a1 = z[..., i].copy(), z[..., i + half].copy()
            z[..., i], z[..., i + half] = a0 * c - a1 * s_, a1 * c + a0 * s_
        return z

    q = np.stack([rot((h[t] @ a["wq"]).reshape(nh, d), t) for t in range(S)])
    k = np.stack([rot((h[t] @ a["wk"]).reshape(g, d), t) for t in range(S)])
    v = (h @ a["wv"]).reshape(S, g, d)
    gate = 1.0 / (1.0 + np.exp(-(h @ a["wg"])))
    out = np.zeros((S, nh * d))
    for t in range(S):
        lo = max(0, t - WINDOW + 1) if windowed else 0
        for n in range(nh):
            s = k[lo:t + 1, n // (nh // g)] @ q[t, n] / math.sqrt(d)
            e = np.exp(s - s.max())
            out[t, n * d:(n + 1) * d] = gate[t, n] * (
                (e / e.sum()) @ v[lo:t + 1, n // (nh // g)])
    with jax.default_matmul_precision("highest"):
        got, gk, gv = ref_lm.attention(DESC, jnp.asarray(x), w, windowed)
    np.testing.assert_allclose(np.asarray(got) - x, out @ a["wo"], rtol=0,
                               atol=5e-5)
    np.testing.assert_allclose(np.asarray(gk), k, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gv), v, rtol=0, atol=1e-5)


@pytest.mark.parametrize("control", [
    {"weights_dtype": jnp.float8_e4m3fn}, {"gate": "none"},
    {"yarn": "plain"}, {"yarn": "scale_all"}, {"rotary": "whole_head"},
    {"rope": "one_base"}, {"window": "full"}, {"router": "sigmoid"},
    {"shared": "none"}],
    ids=["float8_weights", "no_gate", "yarn_plain", "yarn_scale_all",
         "whole_head", "one_base", "full_window", "router_sigmoid",
         "no_shared"])
def test_the_references_controls_are_not_the_reference(control):
    eng = _engine()
    ids = np.random.default_rng(8).integers(0, 256, 60).tolist()
    ref, kv = ref_lm.forward(DESC, eng.params, ids)
    off, off_kv = ref_lm.forward(DESC, eng.params, ids, **control)
    assert np.abs(np.asarray(off) - np.asarray(ref)).max() \
        > 1e-3 * np.abs(np.asarray(ref)).max()
    far = max(float(np.linalg.norm(np.asarray(a[0]) - np.asarray(b[0]))
                    / np.linalg.norm(np.asarray(b[0])))
              for a, b in zip(off_kv, kv))
    assert far > 1e-3
    name = next(iter(control))
    if name != "weights_dtype":
        with pytest.raises(ValueError, match="unknown control"):
            ref_lm.forward(DESC, eng.params, ids[:4], **{name: "nonsense"})


# ------------------------------------------------------------ the share
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Each of 4 expert ranks holds 2 of 8 experts, routes over all 8 and
    computes its own experts' part plus the shared expert, which every rank
    computes alike; the routed parts and the shared expert counted ONCE add up
    to what the uncut reference gives for the whole layer."""
    whole = dict(TINY, num_experts=8, deployment_share=dict(
        TINY["deployment_share"], first_expert=0))
    cfg8 = family.build(whole, LAYERS, 64, jnp.float32).config
    desc8 = family.describe(whole)
    stack = layer_type("gqa_window").init(cfg8, jax.random.PRNGKey(9), 1)
    layer = jax.tree_util.tree_map(lambda a: a[0], stack)
    mlp = layer["mlp"]
    mats = ("w_gate", "w_up", "w_down")
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = ref_lm._rms(x[0], layer["norm2"]["scale"], DESC["norm_eps"])
        gates = ref_lm.route(desc8, h, mlp["router"])
        shared = ref_lm._swiglu(h, *(mlp["shared_" + n] for n in mats))
        want = shared + sum(gates[:, e:e + 1] * ref_lm._swiglu(
            h, *(mlp[n][e] for n in mats)) for e in range(8))
    assert (np.asarray(gates) > 0).sum(1).tolist() == [2] * 50
    np.testing.assert_allclose(np.asarray(gates).sum(1), 2.5, atol=1e-5)
    parts = []
    for first in (0, 2, 4, 6):
        share = dict(whole, num_experts=2, deployment_share=dict(
            whole["deployment_share"], first_expert=first))
        cfg = family.build(share, LAYERS, 64, jnp.float32).config
        cfg.moe_drop_tokens = False
        held = dict(layer, mlp=dict(mlp, **{
            n: mlp[n][first:first + 2] for n in mats}))
        y, _ = mlp_block(cfg, held, x, training=False)
        parts.append(np.asarray(y - x)[0])
    # every rank added the shared expert: counted once, three are taken away
    np.testing.assert_allclose(sum(parts) - 3 * np.asarray(shared),
                               np.asarray(want), rtol=0, atol=2e-5)
    # and the engine's whole layer through the served path is the reference's
    eng = _engine()
    ids = np.random.default_rng(2).integers(0, 256, 20).tolist()
    toks, _ = _serve(eng, [ids], new=4)
    assert max(_regrets(eng, ids, toks[0])) < 1e-5


# --------------------------------------------- MiMo-V2-Flash, as it was
# taken on the parent commit (8f8a03e, jax 0.9.0) by the lines below: the
# tiny model's parameter tree and values, its page and state leaves, the
# lowered text of its decode and chunk programs (``program.apart()``'s) and
# the tokens it serves.  Query heads, rotary share and table by type and the
# head gate default to what that model had: nothing of it may move.
# (PR 61 pinned every paged program's head projections — ``h @ wq``
# behind an optimization barrier, ``transformer.head_projection`` — a
# change these programs were meant to take: the hashes of the programs
# that hold one are its tree's, jax 0.9.0.)
_MIMO_AT_PARENT = {
    "decode": "2936315fbc1eb22b", "chunk": "aae654c4400b86e9",
    "tree": "d1d94ecce786f130", "values": "18af3f357c6126bb",
    "page_leaves": {"k": [2, 48], "v": [2, 32]},
    "state_leaves": {"win_k": [5, [16, 96]], "win_v": [5, [16, 64]]},
    "tokens": [68, 196, 7, 78, 168, 129, 118, 103, 184, 112, 146, 110, 226,
               134, 84, 193, 239, 20, 175, 84, 185, 191, 47, 40]}


@pytest.fixture(scope="module")
def mimo_now():
    model = mimo_v2_model("tiny", max_seq_len=512, moe_held_first=2,
                          moe_held_count=2)
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=8, max_pages_per_seq=64, prefill_chunk=32,
        max_seqs=4, num_pages=160), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    sha = lambda b: hashlib.sha256(b).hexdigest()[:16]  # noqa: E731
    out = {}
    out["decode"] = sha(eng._decode.apart().lower(
        eng.params, eng._pools, S((4,), i32), S((4,), i32), S((4, 64), i32),
        S((4,), jnp.bool_), S((4,), jnp.float32), S((4,), i32),
        S((2,), jnp.uint32)).as_text().encode())
    out["chunk"] = sha(eng._prefill_chunk.apart().lower(
        eng.params, eng._pools, S((32,), i32), S((4,), i32), S((8,), i32),
        S((), i32), S((), i32), S((), i32)).as_text().encode())
    leaves = jax.tree_util.tree_flatten_with_path(eng.params)[0]
    out["tree"] = sha(json.dumps([[jax.tree_util.keystr(p), list(a.shape)]
                                  for p, a in leaves]).encode())
    out["values"] = sha(b"".join(np.asarray(a).tobytes() for _, a in leaves))
    out["page_leaves"] = {k: list(v)
                          for k, v in page_leaves(model.config).items()}
    out["state_leaves"] = {k: [v[0], list(v[1])]
                           for k, v in state_leaves(model.config).items()}
    p = np.random.default_rng(7).integers(0, 256, 45).tolist()
    uid = eng.put(RaggedRequest(prompt_ids=p, max_new_tokens=24))
    toks = []
    while eng.has_work():
        toks += eng.step().get(uid, {"tokens": []})["tokens"]
    out["tokens"] = toks
    return out


@pytest.mark.parametrize("what", sorted(_MIMO_AT_PARENT))
def test_mimo_v2s_tiny_model_is_what_it_was(mimo_now, what):
    assert mimo_now[what] == _MIMO_AT_PARENT[what]


# ------------------------------------- the picks' rows, a row of 3072 wide
def test_the_picks_of_a_3072_wide_row_go_through_the_row_kernels(monkeypatch):
    """ISSUE 57: on the TPU a bfloat16 row of Laguna's hidden 3072 — 12
    word-sublanes — is served by ``dstpu_moe_dispatch`` / ``dstpu_moe_combine``
    as the rows of 2048 and 4096 are, at a chunk call's and a decode call's
    picks: ``_sorted_expert_ffn`` takes its kernel branch and no scatter is
    left in the layer."""
    from deepspeed_tpu.moe.sharded_moe import _sorted_expert_ffn
    from deepspeed_tpu.ops.pallas import moe_dispatch as rows_mod
    from deepspeed_tpu.ops.pallas.grouped_matmul import expert_block_rows

    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    h, f, held, of, k = (CONFIG["hidden_size"], CONFIG["moe_intermediate_size"],
                         CONFIG["num_experts"], 256,
                         CONFIG["num_experts_per_tok"])
    experts = {"w_gate": S((held, h, f), bf), "w_up": S((held, h, f), bf),
               "w_down": S((held, f, h), bf)}

    for on_chip in (False, True):
        monkeypatch.setattr(rows_mod, "on_tpu", lambda: on_chip)

        def tail(xt, key, gate, experts):  # (a trace is kept by function)
            return _sorted_expert_ffn(
                xt, key, gate, k, held, experts, "swiglu",
                expert_block_rows(key.shape[0] / of, xt.dtype))[0]

        for t in (CONFIG["engine"]["prefill_chunk"],
                  CONFIG["engine"]["max_seqs"]):
            text = str(jax.make_jaxpr(tail)(
                S((t, h), bf), S((t * k,), jnp.int32),
                S((t * k,), jnp.float32), experts))
            names = [n in text for n in ("dstpu_moe_dispatch",
                                         "dstpu_moe_combine")]
            assert names == [on_chip] * 2, (on_chip, t)
            assert ("scatter" in text) is not on_chip, (on_chip, t)


def _mosaic_texts(lowered):
    """The Mosaic kernels of a program lowered for the TPU, as text without
    locations (a kernel's serialized body carries its file's line numbers,
    which every edit of the file moves)."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    out = []
    for m in re.finditer(r'backend_config = "((?:[^"\\]|\\.)*)"',
                         lowered.as_text()):
        config = json.loads(m.group(1).replace("\\22", '"')
                            .replace("\\5C", "\\"))
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # (stable_mosaic.*)
        with ctx:
            module = ir.Module.parse(base64.b64decode(
                config["custom_call_config"]["body"]))
            out.append(module.operation.get_asm(enable_debug_info=False))
    return out


# sha256[:16] of the kernels' text, locations stripped, taken on the parent
# commit (dda6f27, jax 0.9.0) by the lines below: a row of whole (8, 128)
# word tiles — every expert cell but Laguna's — must lower to the kernel it
# had (ISSUE 57: "the other cells keep their text")
_ROW_KERNELS_AT_PARENT = {
    ("dispatch", 2048, "bfloat16"): "84cf4b086386de5f",
    ("dispatch", 4096, "bfloat16"): "d6d8077e0b7500fd",
    ("dispatch", 1024, "float32"): "534488ca727aa094",
    ("combine", 2048, "bfloat16"): "b5f5583c4fb54dd9",
    ("combine", 4096, "bfloat16"): "4683abb6eefbc0fa",
    ("combine", 1024, "float32"): "353a29a1b52556b0",
    ("combine_dot", 2048, "bfloat16"): "7fed79a8d64a5fe9",
    ("combine_dot", 4096, "bfloat16"): "0d5d3834462fe717",
    ("combine_dot", 1024, "float32"): "09b2a15e8563c635",
}


@pytest.mark.parametrize("which, h, dtype", sorted(_ROW_KERNELS_AT_PARENT))
def test_a_row_of_whole_word_tiles_lowers_to_the_parents_kernel(
        which, h, dtype, monkeypatch):
    import deepspeed_tpu.utils.platform as plat
    from deepspeed_tpu.ops.pallas import moe_dispatch as rows_mod

    monkeypatch.setattr(plat, "platform", lambda: "tpu")  # compile, not interpret
    S, i32, dt = jax.ShapeDtypeStruct, jnp.int32, jnp.dtype(dtype)
    t, k, bs, blocks = 256, 8, 16, 40
    if which == "dispatch":
        fn = lambda x, rs, nv, nr: rows_mod.dispatch_rows(  # noqa: E731
            x, rs, nv, nr, bs)
        args = (S((t, h), dt), S((blocks * bs,), i32), S((blocks,), i32),
                S((), i32))
    elif which == "combine":
        fn = lambda ys, dest, w: rows_mod.combine_rows(  # noqa: E731
            ys, dest, weights=w)
        args = (S((blocks * bs, h), dt), S((t, k), i32),
                S((t, k), jnp.float32))
    else:
        fn = lambda ys, dest, d: rows_mod.combine_rows(  # noqa: E731
            ys, dest, dot=d)
        args = (S((blocks * bs, h), dt), S((t, k), i32), S((t, h), dt))
    (text,) = _mosaic_texts(jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)))
    assert "dstpu_moe_" + which.split("_")[0] in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _ROW_KERNELS_AT_PARENT[which, h, dtype])


def test_the_row_kernels_time_is_read_in_this_cell_under_a_name_of_its_own():
    """``moe_dispatch_ms_per_step.typed``: the accepted ``op_ms`` reader with
    ``moe_dispatch_ms_per_step``'s own arguments, listed for this cell alone
    (``benchmark/tests/test_laguna_cell.py`` holds the un-suffixed list off
    it), on the layer and the end-to-end metric the un-suffixed one has."""
    cell = "lagunas21-ep8-codeagent-saturated"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}

    def metric_file(name):
        with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            return json.load(f)

    plain = "moe_dispatch_ms_per_step"
    typed, was = metric_file(plain + ".typed"), metric_file(plain)
    assert typed["name"] == plain + ".typed"
    assert typed["reader"] == was["reader"] == "op_ms"
    assert typed["args"] == was["args"]
    assert typed["args"]["contains"] == ["dstpu_moe_dispatch",
                                         "dstpu_moe_combine"]
    entry = listed[plain + ".typed"]
    assert entry["workloads"] == [cell]
    assert cell not in listed[plain]["workloads"]
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == typed[key] == was[key] == listed[plain][key], key
    assert (entry["layer"], entry["moves"]) == ("Serve programs",
                                                "tpot_p50_ms")
