"""MiMo-V2-Flash on the serving path: what a layer keeps follows its type — a
full layer pages of 2 (4) K/V heads, a window layer a ring of 4 (8), keys
wider than values, a learned sink on the window layers, two rotary bases over
a third of a head — behind a dense first layer that is a served run of its
own, over an expert share with a sigmoid router and a selection bias.

Oracles: ``benchmark/reference/swa_moe_lm.py`` (plain float32, every layer at
every position, no cache, no ring, no code shared with the program) for the
engine's programs — logits and cached rows; a dense softmax for the three
kernels (interpreted on the CPU); and, where they import, ``transformers``'
``gpt_oss`` sink softmax and DeepSeek-V3 router for the reference itself.
The tiny key width (24) is not the value's (16) and not a multiple of the
rotary width (8), the tiny window (16) is smaller than the tiny chunk (32) and
larger than the tiny page (8), and the tiny dense layer (128) is wider than an
expert (32).
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

from benchmark.families import mimo_v2 as family  # noqa: E402
from benchmark.reference import swa_moe_lm  # noqa: E402
from deepspeed_tpu.inference.v2 import (InferenceEngineV2,  # noqa: E402
                                        RaggedInferenceConfig, RaggedRequest)
from deepspeed_tpu.inference.v2 import engine_v2, model_runner  # noqa: E402
from deepspeed_tpu.inference.v2.speculative import SpeculativeConfig  # noqa: E402
from deepspeed_tpu.models import (mimo_v2_config, mimo_v2_model,  # noqa: E402
                                  mistral4_model, phi4_flash_model)
from deepspeed_tpu.models.layer_types import (chunk_stops_early,  # noqa: E402
                                              gqa_shape, layer_type,
                                              page_layers,
                                              page_leaves, served_run_configs,
                                              served_runs, state_leaves)
from deepspeed_tpu.models.mimo_v2 import mimo_v2_runs  # noqa: E402
from deepspeed_tpu.models.transformer import mlp_block  # noqa: E402
from deepspeed_tpu.ops.pallas import paged_attention  # noqa: E402
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402
from deepspeed_tpu.ops.pallas.paged_attention import (  # noqa: E402
    merged_keys, paged_decode_attention, split_keys, split_queries)
from deepspeed_tpu.telemetry.spans import get_span_recorder  # noqa: E402

with open(os.path.join(ROOT, "benchmark", "configs",
                       "mimo-v2-flash-ep16-serve.json")) as _f:
    CONFIG = json.load(_f)
TINY = dict(CONFIG, **CONFIG["tiny"])
DESC = family.describe(TINY)
ENGINE = CONFIG["tiny_engine"]
CHUNK, PS, MP = (ENGINE["prefill_chunk"], ENGINE["page_size"],
                 ENGINE["max_pages_per_seq"])
WINDOW = DESC["sliding_window"]


def _engine(seed=0, **over):
    model = family.build(TINY, TINY["num_hidden_layers"], PS * MP,
                         jnp.float32)
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
    ref, _ = swa_moe_lm.forward(DESC, eng.params, prompt + toks[:-1],
                                logits_from=len(prompt) - 1)
    return [float(row.max() - row[t]) / float(np.abs(row).max())
            for row, t in zip(np.asarray(ref), toks)]


# ------------------------------------------------------------ the description
def test_what_a_layer_keeps_follows_its_type_and_the_pools_follow():
    cfg = mimo_v2_model("tiny").config
    full, win = gqa_shape(cfg, "gqa_full"), gqa_shape(cfg, "gqa_window")
    assert (full.kv_heads, full.k_dim, full.v_dim, full.rot, full.window,
            full.sink) == (2, 24, 16, 8, 0, False)
    assert (win.kv_heads, win.k_dim, win.v_dim, win.rot, win.window,
            win.sink) == (4, 24, 16, 8, 16, True)
    assert (full.theta, win.theta) == (5e6, 1e4)
    assert page_layers(cfg) == 2
    assert page_leaves(cfg) == {"k": (2, 48), "v": (2, 32)}
    assert state_leaves(cfg) == {"win_k": (5, (16, 96), None),
                                 "win_v": (5, (16, 64), None)}
    eng = _engine()
    P, S = ENGINE["num_pages"] + 1, ENGINE["max_seqs"] + 1
    shapes = {n: a.shape for n, a in eng._pools.items()}
    # two page leaves of different widths over the 2 full layers, rings of
    # another head count over the 5 window layers, and no pool of 7 layers
    assert shapes == {"k": (2, P, PS, 48), "v": (2, P, PS, 32),
                      "win_k": (5, S, WINDOW, 96), "win_v": (5, S, WINDOW, 64),
                      "moe_stats": (5,)}
    # the published widths: 2,560 B a token a full layer, 640 KiB a ring
    big = mimo_v2_config("309b", n_layers=7)
    assert page_leaves(big) == {"k": (2, 768), "v": (2, 512)}
    assert state_leaves(big) == {"win_k": (5, (128, 1536), None),
                                 "win_v": (5, (128, 1024), None)}
    assert 2 * (768 + 512) == 2560 and 128 * 2 * (1536 + 1024) == 655360


def test_the_dense_first_layer_is_a_served_run_of_its_own_width():
    cfg = mimo_v2_model("tiny", moe_held_first=2, moe_held_count=2).config
    runs = served_runs(cfg)
    assert [(tuple(t.name for t in types), n) for types, n in runs] == [
        (("gqa_full",), 1), (("gqa_window",) * 5 + ("gqa_full",), 1)]
    dense, experts = served_run_configs(cfg)
    assert (dense.moe_experts, dense.ffn_size) == (0, 128)
    assert (experts.moe_experts, experts.ffn_size) == (8, 32) and \
        experts is cfg
    params = jax.eval_shape(mimo_v2_model(config=cfg).init_params,
                            jax.random.PRNGKey(0))
    (first,), period = params["layers"]
    assert first["mlp"]["w_up"].shape == (1, 64, 128)
    assert "router" not in first["mlp"]
    for tree in period:
        assert tree["mlp"]["w_up"].shape == (1, 2, 64, 32)
        assert tree["mlp"]["router"].shape == (1, 64, 8)
    assert [("sink" in t["attn"]) for t in period] == [True] * 5 + [False]
    # at the published widths the dense run reads 16384 and the others 2048
    big = mimo_v2_config("309b", n_layers=7, moe_held_count=16)
    shapes = jax.eval_shape(mimo_v2_model(config=big).init_params,
                            jax.random.PRNGKey(0))["layers"]
    assert shapes[0][0]["mlp"]["w_gate"].shape == (1, 4096, 16384)
    assert shapes[1][0]["mlp"]["w_gate"].shape == (1, 16, 4096, 2048)
    assert shapes[1][0]["attn"]["wk"].shape == (1, 4096, 8 * 192)
    assert shapes[1][5]["attn"]["wv"].shape == (1, 4096, 4 * 128)


@pytest.mark.parametrize("layers,runs", [
    (7, [1, 6]), (13, [1, 12]), (48, [1, 5, 42])])
def test_the_published_pattern_as_runs(layers, runs):
    got = mimo_v2_runs(layers)
    assert [len(p) * n for p, n in got] == runs
    kinds = [k for p, n in got for _ in range(n) for k in p]
    if layers == 48:
        assert [int(k == "gqa_window") for k in kinds] == \
            CONFIG["published"]["hybrid_layer_pattern"]
    with pytest.raises(ValueError, match="whole periods"):
        mimo_v2_runs(layers + 2)


def test_a_prologue_that_ends_inside_a_run_is_refused():
    cfg = mimo_v2_config("tiny", dense_layers=3)
    with pytest.raises(ValueError, match="the prologue is whole runs"):
        served_run_configs(cfg)
    lfm2_like = mimo_v2_config("tiny", layer_types=("attn",) * 7)
    with pytest.raises(NotImplementedError, match="'conv' mixer"):
        served_runs(lfm2_like)


# -------------------------------------------- the programs against the reference
@pytest.mark.parametrize("n,steps,kernels", [
    (10, 40, "xla"), (24, 6, "xla"), (70, 6, "xla"),
    (24, 6, "interpreted"), (70, 6, "interpreted")],
    ids=["shorter_than_the_window_then_two_wraps-xla",
         "across_the_window_inside_a_chunk-xla",
         "across_two_chunk_boundaries-xla",
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
    ref, kv = swa_moe_lm.forward(DESC, eng.params, toks, logits_from=n - 1)
    np.testing.assert_allclose(np.stack(rows), ref, rtol=0, atol=3e-5)
    S, used = len(toks), -(-len(toks) // PS)
    seen = {"full": 0, "window": 0}
    for (k, v), windowed in zip(kv, DESC["window_layers"]):
        sh = gqa_shape(eng.cfg, "gqa_window" if windowed else "gqa_full")
        if windowed:
            l, at = seen["window"], np.arange(S - WINDOW, S)
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


def test_put_step_serves_it_and_sequences_do_not_see_each_others_cache():
    """Three sequences interleaved in different slots, prefilling and decoding
    in the same steps, each against the reference alone: a neighbour's ring
    or page would move its logits.  And what the steps' spans carry."""
    eng = _engine()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (70, 13, 100)]
    get_span_recorder().clear()
    toks, steps = _serve(eng, prompts, new=20)
    for p, t in zip(prompts, toks):
        assert len(t) == 20 and max(_regrets(eng, p, t)) < 1e-5
    eng.assert_no_leaks()
    assert eng.state_slots.in_use == 0
    # the step's counters, from the host's own book: visible positions once
    # (not a layer), the rings' share of them, the pages held
    dec = [s for s in steps if s["decode_rows"] == 3]
    assert dec and all(
        s["window_kv_tokens"] == WINDOW * 3 < s["full_kv_tokens"]
        for s in dec)
    assert all(b["full_kv_tokens"] == a["full_kv_tokens"] + 3
               for a, b in zip(dec, dec[1:]))
    alone = next(s for s in steps if s["decode_rows"] == 1)
    assert alone["full_kv_tokens"] == alone["window_kv_tokens"] == 13 + 1
    spans = get_span_recorder().spans()
    held = [sp.attrs["page_tokens_in_use"] for sp in spans
            if sp.name == "serve_step"]
    assert PS * sum(-(-len(p) // PS) for p in prompts) <= max(held) \
        <= PS * sum(-(-(len(p) + 20) // PS) for p in prompts)
    assert not any(h % PS for h in held)
    chunks = [sp.attrs for sp in spans if sp.name == "prefill"
              and sp.cat == "phase"]
    assert sorted(c["ctx_tokens"] for c in chunks) == [0, 0, 0, 32, 32, 64,
                                                       64, 96]
    assert all(s["long_rows"] == 0 for s in steps if "long_rows" in s)
    assert all(k in dec[0] for k in ("moe_local_picks", "moe_layer_calls"))


def test_long_rows_counts_the_rows_over_the_threshold(monkeypatch):
    monkeypatch.setattr(engine_v2, "LONG_ROW_TOKENS", 50)
    eng = _engine()
    rng = np.random.default_rng(5)
    _, steps = _serve(eng, [rng.integers(0, 256, n).tolist()
                            for n in (70, 13, 100)], new=4)
    assert max(s.get("long_rows", 0) for s in steps) == 2


def test_a_preempted_sequence_is_prefilled_again_and_goes_on_the_same():
    eng = _engine()
    prompt = np.random.default_rng(2).integers(0, 256, 45).tolist()

    def serve(preempt_after):
        uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=24))
        toks, cut = [], False
        while eng.has_work():
            toks += eng.step().get(uid, {"tokens": []})["tokens"]
            if not cut and len(toks) >= preempt_after:
                eng._preempt(eng._find_slotted(uid))
                cut = True
        return toks

    cut, whole = serve(4), serve(10 ** 9)
    assert cut == whole and max(_regrets(eng, prompt, whole)) < 1e-5
    assert eng.decode_stats()["state_slot_preemptions"] == 1
    eng.assert_no_leaks()


def test_read_kv_gives_the_pages_and_the_rings_in_position_order():
    eng = _engine()
    prompt = np.random.default_rng(3).integers(0, 256, 41).tolist()
    uid = eng.put(RaggedRequest(prompt_ids=prompt, max_new_tokens=30))
    toks = []
    while len(toks) < 9:
        toks += eng.step().get(uid, {"tokens": []})["tokens"]
    kept = eng.read_kv(uid)
    n = len(prompt) + len(toks) - 1
    _, kv = swa_moe_lm.forward(DESC, eng.params, prompt + toks[:-1])
    assert [r["first"] for r in kept] == [
        n - WINDOW if w else 0 for w in DESC["window_layers"]]
    for got, (k, v) in zip(kept, kv):
        lo = got["first"]
        np.testing.assert_allclose(got["k"], np.asarray(k)[lo:n], atol=1e-5)
        np.testing.assert_allclose(got["v"], np.asarray(v)[lo:n], atol=1e-5)
    eng.abort_all("done")


@pytest.mark.parametrize("what", ["training", "prefix_cache", "speculative",
                                  "whole_prompt", "kv_quant", "export"])
def test_what_cannot_serve_rings_beside_pages_is_refused_by_name(what):
    if what == "training":
        with pytest.raises(NotImplementedError,
                           match="sink and a window in the flash backward"):
            mimo_v2_model("tiny").loss_fn(None, None, None)
        with pytest.raises(NotImplementedError, match="is served only"):
            layer_type("gqa_window").mix()
    elif what == "prefix_cache":
        with pytest.raises(ValueError, match="window's ring.*win_k"):
            _engine(enable_prefix_cache=True)
    elif what == "speculative":
        with pytest.raises(ValueError, match="window's ring"):
            _engine(speculative=SpeculativeConfig(mode="ngram", k=2))
    elif what == "whole_prompt":
        with pytest.raises(ValueError, match="prefill_chunk"):
            _engine(prefill_chunk=0)
    elif what == "kv_quant":
        with pytest.raises(ValueError, match="kv_quant.*split"):
            _engine(kv_quant=True)
    else:
        eng = _engine()
        uid = eng.put(RaggedRequest(prompt_ids=[1, 2, 3], max_new_tokens=4))
        eng.step()
        with pytest.raises(NotImplementedError, match="a bundle holds pages"):
            eng.export_sequence(uid)
        eng.abort_all("done")


# ------------------------------------------------------------------ the kernels
def _dense(q, k, v, vis, scale, sink=None):
    """A dense softmax in float64: ``q [T, NH, dk]``, ``k [S, G, dk]``, ``v
    [S, G, dv]``, ``vis [T, S]``; ``sink [NH]`` joins the denominator."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    g = q.shape[1] // k.shape[1]
    s = np.einsum("tnd,snd->nts", q, np.repeat(k, g, 1)) * scale
    s = np.where(vis[None], s, -np.inf)
    m = s.max(-1, keepdims=True)
    if sink is not None:
        m = np.maximum(m, np.asarray(sink, np.float64)[:, None, None])
    e = np.exp(s - m)
    den = e.sum(-1, keepdims=True)
    if sink is not None:
        den = den + np.exp(np.asarray(sink, np.float64)[:, None, None] - m)
    return np.einsum("nts,snd->tnd", e / den, np.repeat(v, g, 1))


@pytest.mark.parametrize("window", [0, 24], ids=["full", "window"])
@pytest.mark.parametrize("with_sink", [False, True], ids=["plain", "sink"])
def test_flash_with_a_sink_a_window_and_wider_keys_is_the_dense_softmax(
        window, with_sink):
    """Interpret mode, keys of 24 beside values of 16, 8 query heads over 2
    K/V heads, blocks smaller than the sequence so that tiles are skipped; a
    window's first keys masked from the front (``k_first``)."""
    rng = np.random.default_rng(4)
    T, S, NH, G, dk, dv, off, k_first = 40, 104, 8, 2, 24, 16, 64, 5
    q = rng.normal(size=(1, T, NH, dk)).astype(np.float32)
    k = rng.normal(size=(1, S, G, dk)).astype(np.float32)
    v = rng.normal(size=(1, S, G, dv)).astype(np.float32)
    sink = rng.normal(size=NH).astype(np.float32) if with_sink else None
    rows, cols = off + np.arange(T)[:, None], np.arange(S)[None]
    vis = cols <= rows
    if window:
        vis = vis & (rows - cols < window) & (cols >= k_first)
    got = flash_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), causal=True,
        q_offset=off, sm_scale=0.2, block_q=16, block_k=16,
        sink=None if sink is None else jnp.asarray(sink),
        **({"window": window, "k_first": k_first} if window else {}))
    assert got.shape == (1, T, NH, dv)
    np.testing.assert_allclose(np.asarray(got[0]),
                               _dense(q[0], k[0], v[0], vis, 0.2, sink),
                               rtol=0, atol=2e-5)
    with pytest.raises(ValueError, match="forward-only kernel"):
        flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))


@pytest.mark.parametrize("table", ["prefetched", "by_grid_step"])
@pytest.mark.parametrize("kind", ["pages", "ring"])
def test_the_decode_kernel_over_split_keys_is_the_dense_softmax(
        kind, table, monkeypatch):
    """Interpret mode: keys stored split (plain lanes, then pairs of heads'
    rotary lanes) beside narrower values; NaN planted in every page past a
    row's length, in the key rows of its last page past it, in an inactive
    row's pages and in another layer's; the ring's walk takes a sink.  ``by_grid_step``: the table handed
    to the kernel a grid step's rows at a time, as a table too large for
    scalar memory is."""
    if table == "by_grid_step":
        monkeypatch.setattr(paged_attention, "_TABLE_PREFETCH_BYTES", 0)
    rng = np.random.default_rng(6)
    B, NH, G, dk, dv, rot, ps, MPk = 6, 8, 4, 24, 16, 8, 8, 5
    P = B * MPk
    lengths = [37, 8, 0, 1, 40, 16]
    k = rng.normal(size=(2, P, ps, G, dk)).astype(np.float32)
    v = rng.normal(size=(2, P, ps, G, dv)).astype(np.float32)
    q = rng.normal(size=(B, NH, dk)).astype(np.float32)
    sink = rng.normal(size=NH).astype(np.float32) if kind == "ring" else None
    tab = np.arange(P, dtype=np.int32).reshape(B, MPk)
    for b, n in enumerate(lengths):  # what must never be read
        flat_k = k[1, tab[b]].reshape(MPk * ps, G, dk)
        flat_v = v[1, tab[b]].reshape(MPk * ps, G, dv)
        # (a value row of the row's own last page is read and weighted 0:
        # the engine's pool holds zeros or a former owner's values there)
        flat_k[n:], flat_v[-(-n // ps) * ps:] = np.nan, np.nan
        k[1, tab[b]] = flat_k.reshape(MPk, ps, G, dk)
        v[1, tab[b]] = flat_v.reshape(MPk, ps, G, dv)
    k[0], v[0] = np.nan, np.nan  # another layer's pages
    got = paged_decode_attention(
        split_queries(jnp.asarray(q), rot, G),
        split_keys(jnp.asarray(k), rot), jnp.asarray(v).reshape(2, P, ps, -1),
        jnp.asarray(tab), jnp.asarray(np.maximum(np.asarray(lengths) - 1, 0)),
        layer=1, active=jnp.asarray(np.asarray(lengths) > 0), scale=0.2,
        rot=rot, sink=None if sink is None else jnp.asarray(sink),
        name="dstpu_window_decode" if kind == "ring" else "dstpu_paged_decode")
    assert got.shape == (B, NH, dv)
    for b, n in enumerate(lengths):
        if not n:
            assert not np.asarray(got[b]).any()
            continue
        kk = k[1, tab[b]].reshape(-1, G, dk)[:n]
        vv = v[1, tab[b]].reshape(-1, G, dv)[:n]
        want = _dense(q[b][None], kk, vv, np.ones((1, n), bool), 0.2, sink)
        np.testing.assert_allclose(np.asarray(got[b]), want[0], rtol=0,
                                   atol=2e-5)


def test_split_keys_and_queries_score_as_whole_heads_do():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(3, 8, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(5, 4, 24)), jnp.float32)
    rows = split_keys(k, 8)
    assert rows.shape == (5, 96)
    np.testing.assert_array_equal(np.asarray(merged_keys(rows, 8, 4)),
                                  np.asarray(k))
    # plain lanes of head h at [16 h, 16 (h + 1)), rotary at 64 + [8 h, ...)
    np.testing.assert_array_equal(np.asarray(rows[:, 16:32]),
                                  np.asarray(k[:, 1, 8:]))
    np.testing.assert_array_equal(np.asarray(rows[:, 64 + 8:64 + 16]),
                                  np.asarray(k[:, 1, :8]))
    q2 = split_queries(q, 8, 4)
    assert q2.shape == (3, 8, 32)
    for n in range(8):
        h = n // 2
        pair = rows[:, 64 + 16 * (h // 2):64 + 16 * (h // 2 + 1)]
        got = q2[:, n, :16] @ rows[:, 16 * h:16 * (h + 1)].T \
            + q2[:, n, 16:] @ pair.T
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(q[:, n] @ k[:, h].T),
                                   rtol=0, atol=1e-5)


# --------------------------------------------- the reference against the source
def _layer_weights(rng, windowed, H=64):
    g = DESC["kv_heads_window" if windowed else "kv_heads_full"]
    nh, dk, dv = (DESC["num_attention_heads"], DESC["head_dim"],
                  DESC["v_head_dim"])
    w = {"norm1": {"scale": jnp.ones((H,), jnp.float32)}, "attn": {
        name: jnp.asarray(rng.normal(size=shape) * 0.2, jnp.float32)
        for name, shape in (("wq", (H, nh * dk)), ("wk", (H, g * dk)),
                            ("wv", (H, g * dv)), ("wo", (nh * dv, H)))}}
    w["attn"]["sink"] = jnp.asarray(rng.normal(size=nh), jnp.float32)
    return w


def test_the_references_sink_softmax_is_gpt_oss_and_its_router_deepseek_v3s():
    """The reference's window layer against ``gpt_oss``'s eager attention (the
    sink as one more column of the softmax, dropped after it) over the
    reference's own rotated queries and keys and scaled values, and its
    router against ``DeepseekV3TopkRouter``, weights copied, float32."""
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers")
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3 import modeling_deepseek_v3 as ds
    from transformers.models.gpt_oss import modeling_gpt_oss as oss

    rng = np.random.default_rng(8)
    S, H = 40, 64
    w = _layer_weights(rng, windowed=True)
    x = jnp.asarray(rng.normal(size=(S, H)), jnp.float32)
    nh, g, dk, dv = 8, DESC["kv_heads_window"], 24, 16
    with jax.default_matmul_precision("highest"):
        got, k, v = swa_moe_lm.attention(DESC, x, w, windowed=True)
        h = swa_moe_lm._rms(x, 1.0, DESC["norm_eps"])
        q = swa_moe_lm._rotate((h @ w["attn"]["wq"]).reshape(S, nh, dk),
                               DESC["rotary_dim"], DESC["swa_rope_theta"],
                               jnp.arange(S))
    t = lambda a: torch.tensor(np.asarray(a)).transpose(0, 1)[None]  # noqa: E731
    d = np.arange(S)[:, None] - np.arange(S)[None]
    mask = torch.tensor(np.where((d >= 0) & (d < WINDOW), 0.0, -np.inf)
                        .astype(np.float32))[None, None]
    module = types.SimpleNamespace(
        sinks=torch.tensor(np.asarray(w["attn"]["sink"])),
        num_key_value_groups=nh // g, training=False)
    with torch.no_grad():
        out, _ = oss.eager_attention_forward(module, t(q), t(k), t(v), mask,
                                             scaling=dk ** -0.5)
    want = out[0].reshape(S, nh * dv).numpy() @ np.asarray(w["attn"]["wo"])
    np.testing.assert_allclose(np.asarray(got - x), want, rtol=0, atol=2e-5)

    cfg = DeepseekV3Config(hidden_size=H, n_routed_experts=8,
                           num_experts_per_tok=2, n_group=1, topk_group=1,
                           norm_topk_prob=True, routed_scaling_factor=1.0)
    router = ds.DeepseekV3TopkRouter(cfg).eval()
    wr = rng.normal(size=(H, 8)).astype(np.float32)
    bias = (rng.normal(size=8) * 0.1).astype(np.float32)
    with torch.no_grad():
        router.weight.copy_(torch.tensor(wr.T.copy()))
        router.e_score_correction_bias.copy_(torch.tensor(bias))
        idx, weights = router(torch.tensor(np.asarray(h))[None])
    want = np.zeros((S, 8), np.float32)
    np.put_along_axis(want, idx.numpy(), weights.numpy(), axis=1)
    whole = dict(DESC, experts_first=0, experts_held=8)
    with jax.default_matmul_precision("highest"):
        mine = swa_moe_lm.route(whole, h, jnp.asarray(wr), jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(mine), want, rtol=0, atol=1e-6)


def test_the_references_layers_are_their_equations_token_by_token():
    """Each attention type of the reference against a loop over positions
    written from the equations alone: rotary on the first 8 of 24 dimensions
    with the type's base, values scaled, a window of 16 with the sink."""
    rng = np.random.default_rng(9)
    S, H, nh, dk, dv = 37, 64, 8, 24, 16
    x = rng.normal(size=(S, H)).astype(np.float32)
    for windowed in (False, True):
        w = _layer_weights(rng, windowed)
        a = {n: np.asarray(m, np.float64) for n, m in w["attn"].items()}
        g = DESC["kv_heads_window" if windowed else "kv_heads_full"]
        theta = 1e4 if windowed else 5e6
        h = x / np.sqrt((x.astype(np.float64) ** 2).mean(-1, keepdims=True)
                        + 1e-5)

        def rot(z, t):
            z = z.copy()
            for i in range(4):
                ang = t * theta ** (-2.0 * i / 8)
                a0, a1 = z[..., i].copy(), z[..., i + 4].copy()
                z[..., i] = a0 * math.cos(ang) - a1 * math.sin(ang)
                z[..., i + 4] = a1 * math.cos(ang) + a0 * math.sin(ang)
            return z

        q = np.stack([rot((h[t] @ a["wq"]).reshape(nh, dk), t)
                      for t in range(S)])
        k = np.stack([rot((h[t] @ a["wk"]).reshape(g, dk), t)
                      for t in range(S)])
        v = 0.707 * (h @ a["wv"]).reshape(S, g, dv)
        out = np.zeros((S, nh * dv))
        for t in range(S):
            lo = max(0, t - WINDOW + 1) if windowed else 0
            for n in range(nh):
                s = k[lo:t + 1, n // (nh // g)] @ q[t, n] / math.sqrt(dk)
                e = np.exp(s - s.max())
                den = e.sum() + (math.exp(a["sink"][n] - s.max())
                                 if windowed else 0.0)
                out[t, n * dv:(n + 1) * dv] = (e / den) @ v[lo:t + 1,
                                                            n // (nh // g)]
        with jax.default_matmul_precision("highest"):
            got, gk, gv = swa_moe_lm.attention(DESC, jnp.asarray(x), w,
                                               windowed)
        np.testing.assert_allclose(np.asarray(got) - x, out @ a["wo"],
                                   rtol=0, atol=2e-5)
        np.testing.assert_allclose(np.asarray(gk), k, rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gv), v, rtol=0, atol=1e-5)


@pytest.mark.parametrize("control", [
    {"weights_dtype": jnp.float8_e4m3fn}, {"sink": "none"},
    {"window": "full"}, {"rope": "one_base"}, {"rotary": "whole_head"},
    {"router": "softmax"}],
    ids=["float8_weights", "no_sink", "full_window", "one_base", "whole_head",
         "router_softmax"])
def test_the_references_controls_are_not_the_reference(control):
    eng = _engine()
    ids = np.random.default_rng(8).integers(0, 256, 60).tolist()
    ref, kv = swa_moe_lm.forward(DESC, eng.params, ids)
    off, off_kv = swa_moe_lm.forward(DESC, eng.params, ids, **control)
    assert np.abs(np.asarray(off) - np.asarray(ref)).max() \
        > 1e-3 * np.abs(np.asarray(ref)).max()
    far = max(float(np.linalg.norm(np.asarray(a[0]) - np.asarray(b[0]))
                    / np.linalg.norm(np.asarray(b[0])))
              for a, b in zip(off_kv, kv))
    assert far > 1e-3
    with pytest.raises(ValueError, match="unknown"):
        swa_moe_lm.forward(DESC, eng.params, ids[:4],
                           **{next(iter(control)): "nonsense"}
                           ) if "weights_dtype" not in control else \
            swa_moe_lm.forward(DESC, eng.params, ids[:4], sink="nonsense")


# ------------------------------------------------------------ the share
def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of 4 expert ranks holds 2 of 8 experts, routes over all 8 and
    computes its own experts' part; the parts add up to what the uncut
    reference gives for the whole layer (no shared expert to count once)."""
    whole = dict(TINY, n_routed_experts=8, deployment_share=dict(
        TINY["deployment_share"], first_expert=0))
    cfg8 = family.build(whole, 7, 64, jnp.float32).config
    desc8 = family.describe(whole)
    stack = layer_type("gqa_full").init(cfg8, jax.random.PRNGKey(9), 1)
    layer = jax.tree_util.tree_map(lambda a: a[0], stack)
    mlp = layer["mlp"]
    x = jnp.asarray(np.random.default_rng(9).normal(size=(1, 50, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = swa_moe_lm._rms(x[0], layer["norm2"]["scale"], 1e-5)
        gates = swa_moe_lm.route(desc8, h, mlp["router"], mlp["router_bias"])
        want = sum(gates[:, e:e + 1] * swa_moe_lm._swiglu(
            h, mlp["w_gate"][e], mlp["w_up"][e], mlp["w_down"][e])
            for e in range(8))
    assert (np.asarray(gates) > 0).sum(1).tolist() == [2] * 50
    parts = []
    for first in (0, 2, 4, 6):
        share = dict(whole, n_routed_experts=2, deployment_share=dict(
            whole["deployment_share"], first_expert=first))
        cfg = family.build(share, 7, 64, jnp.float32).config
        cfg.moe_drop_tokens = False
        held = dict(layer, mlp=dict(mlp, **{
            n: mlp[n][first:first + 2] for n in ("w_gate", "w_up", "w_down")}))
        y, _ = mlp_block(cfg, held, x, training=False)
        parts.append(np.asarray(y - x)[0])
    np.testing.assert_allclose(sum(parts), np.asarray(want), rtol=0,
                               atol=2e-5)


# ------------------------------ programs without a prologue, as they were
# sha256[:16] of ``lower(...).as_text()`` of Phi-4-mini-flash's and
# Mistral-Small-4's serving programs at the sizes below, taken on the parent
# commit (6849232, jax 0.9.0) before a served run had a feed-forward part of
# its own, the rings a helper of their own and ``_ffn`` a look at the layer's
# parameters: a stack without a prologue must lower to what it did.  A PR
# that means to change these programs takes the hashes anew from its parent.
# (Since PR 53 a serving program takes its inputs packed: the text pinned
# here is ``program.apart()``'s, the function behind the slices, which is
# the parent's.)
# (PR 61 pinned every paged program's head projections — ``h @ wq``
# behind an optimization barrier, ``transformer.head_projection`` — a
# change these programs were meant to take: the hashes of the programs
# that hold one are its tree's, jax 0.9.0.)
_PARENT_HLO = {"phi4_flash.decode": "971ff8b37e892773",
               "phi4_flash.chunk": "d208b3b81bd4cd27",
               "mistral4.decode": "2f00d02f2035b50a",
               "mistral4.chunk": "4440fcd7665ff91e"}


@pytest.mark.parametrize("program", sorted(_PARENT_HLO))
def test_models_without_a_prologue_lower_as_before(program):
    name, prog = program.split(".")
    model, ps = ((phi4_flash_model("tiny", max_seq_len=128), 8)
                 if name == "phi4_flash" else
                 (mistral4_model("tiny", max_seq_len=128, moe_held_first=2,
                                 moe_held_count=2), 4))
    eng = InferenceEngineV2(model, RaggedInferenceConfig(
        dtype="fp32", page_size=ps, max_pages_per_seq=16, prefill_chunk=16,
        max_seqs=4, num_pages=80), seed=0)
    i32, S = jnp.int32, jax.ShapeDtypeStruct
    B, MP_ = 4, 16
    if prog == "decode":
        low = eng._decode.apart().lower(
            eng.params, eng._pools, S((B,), i32), S((B,), i32),
            S((B, MP_), i32), S((B,), jnp.bool_), S((B,), jnp.float32),
            S((B,), i32), S((2,), jnp.uint32))
    else:
        slot = (S((), i32),) if eng._state else ()
        low = eng._prefill_chunk.apart().lower(
            eng.params, eng._pools, S((16,), i32), S((16 // ps,), i32),
            S((MP_ if chunk_stops_early(eng.cfg) else 4,), i32), S((), i32),
            S((), i32), *slot)
    got = hashlib.sha256(low.as_text().encode()).hexdigest()[:16]
    assert got == _PARENT_HLO[program]
